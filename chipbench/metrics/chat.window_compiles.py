"""Programs lowered inside the measured window (a jax.monitoring count);
every shape is warmed in set-up, so this should read 0."""


def read(run):
    return run.counters.get("window_compiles")
