"""Programs lowered (compiled or read from the cache) inside the traced
window (jax.monitoring lowering events); 0 when warm-up covered every shape."""


def read(rec):
    return float(rec["compiles_in_window"])
