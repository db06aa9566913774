"""inner_step_us: device time of one sequential inner step, in us.

From the traced slice: the operation that runs most often on the first
traced chip is in the innermost loop (the inner-step ``lax.scan``), and
the median distance between its starts is the time of one step, the
loop's own gaps included. Nothing is returned where no operation
repeats.
"""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    steps = run.trace.inner_steps(run.trace.devices[0])
    if steps is None:
        return None
    return steps[1] * 1e6
