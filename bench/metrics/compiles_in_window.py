"""Programs the window asked XLA for that the warm-up had not made,
compiled or read back from the persistent cache (``jax.monitoring``)."""


def read(run):
    return run.compiles_in_window
