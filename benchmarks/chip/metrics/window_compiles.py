"""Backend compiles (or loads from the compile cache) inside the measured
window, as jax.monitoring reports them; 0 when set-up warmed everything."""


def read(run):
    return run.window_compiles
