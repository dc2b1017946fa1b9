"""window_compiles: backend compiles inside the measured window, from
JAX's compile-duration events.  Everything the window runs is compiled
in set-up, so it should read 0."""


def read(run):
    return run.compiles_window
