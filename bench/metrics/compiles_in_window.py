"""Backend compiles inside the window, counted from ``jax.monitoring``'s
compile events (a persistent-cache hit is not a compile)."""


def read(run):
    return run.counters.get("compiles_in_window")
