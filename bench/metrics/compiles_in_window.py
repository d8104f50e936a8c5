"""Programs compiled or loaded from the compile cache while the window ran
(``jax.monitoring`` backend-compile events); 0 when warm-up reached every
shape."""


def read(run):
    return run.compiles_in_window
