"""window_compiles: XLA compilations inside the measured window.

Counted from JAX's ``BACKEND_COMPILE_EVENT``; a warm window has none.
"""


def read(run):
    return float(run.window_compiles)
