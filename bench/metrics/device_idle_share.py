"""device_idle_share: % of the traced slice in which no operation ran.

1 minus the union of the device's operation intervals over the length of
the traced window, averaged over the chips the cell uses.
"""


def read(run):
    if run.trace is None:
        return None
    idle = run.trace.idle_share()
    return None if idle is None else 100.0 * idle
