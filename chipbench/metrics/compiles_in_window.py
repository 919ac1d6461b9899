"""Programs lowered (then compiled or loaded from the cache) while the
window was open; JAX's own compile events.  Should be 0."""


def read(run):
    return float(run.compiles_in_window)
