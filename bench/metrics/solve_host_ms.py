"""solve_host_ms: the host's share of a ``repro.solve`` call, in ms.

Each window solve's wall time (the call to its result on the host) minus
the driver time the program reports itself (``RunResult.wall_s``: the
driver call to ``block_until_ready``), averaged over the window. It is
the entry layer's own work: coercing and sharding the problem, the step
size, the key, the copies of the result to the host.
"""


def read(run):
    if not run.solves:
        return None
    host = [s.wall_s - s.driver_s for s in run.solves]
    return 1e3 * sum(host) / len(host)
