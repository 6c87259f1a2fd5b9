"""Shared arithmetic of the model-ops utilisation readers (not a metric):
operations the model needs, over device-or-window seconds times the chip's
int8 peak (bf16 work counts against the int8 peak too, so the share is a
lower bound)."""
from perfbench import trace


def over_program(rec, phase):
    t = trace.matching(rec["trace"]["module_time"], [rec["programs"][phase]])
    ops = rec["ops"][phase]
    return 100.0 * ops / (t * rec["peaks"]["int8_ops"]) if t > 0 and ops \
        else None
