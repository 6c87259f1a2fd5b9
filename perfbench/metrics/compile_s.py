"""Seconds of set-up spent tracing, lowering and compiling, persistent
cache reads included (jax.monitoring compile durations)."""


def read(rec):
    return rec["compile_s"]
