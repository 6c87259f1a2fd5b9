"""Device milliseconds of the decode-chunk programs in the trace, per decode
step they ran."""
from perfbench import trace


def read(rec):
    t = trace.matching(rec["trace"]["module_time"], [rec["programs"]["decode"]])
    steps = rec["decode_steps"]
    return 1e3 * t / steps if steps and t > 0 else None
