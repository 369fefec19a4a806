"""XLA compiles and persistent-cache loads inside the measured window, from
JAX's monitoring events: 0 when set-up warmed every program the window
runs."""


def read(run):
    return run.window_compiles
