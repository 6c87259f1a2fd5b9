"""Device milliseconds of the prefill programs in the trace, per thousand
real (unpadded) prompt tokens they ran."""
from perfbench import trace


def read(rec):
    t = trace.matching(rec["trace"]["module_time"],
                       [rec["programs"]["prefill"]])
    ktok = rec["prefill_tokens"] / 1e3
    return 1e3 * t / ktok if ktok and t > 0 else None
