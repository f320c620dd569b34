"""Programs compiled, or loaded from the persistent cache, inside the window
(``jax.monitoring`` backend-compile events): each one stalls serving."""


def read(run):
    return run.win.compiles
