"""setup_s (s): from the start of the process to the opening of the
window: imports, graph and plan (generated or loaded), session and
front door, and the warm-up of every shape, compiles included."""


def read(run):
    return run.setup_s
