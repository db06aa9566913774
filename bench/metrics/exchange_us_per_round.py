"""exchange_us_per_round: device time of the collectives of one round.

From the traced slice, on the first traced chip: the time of the
collective operations (the per-round ``pmean`` of the spmd runner and the
one inside its rel-grad-norm), grouped into exchanges by the gaps between
them, divided by the exchanges seen. Nothing is returned where the slice
holds no collective.
"""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    groups = run.trace.exchanges(run.trace.devices[0])
    if not groups:
        return None
    return 1e6 * sum(sec for sec, _ in groups) / len(groups)
