"""Model operations of every token processed in the traced window (prefill
and decode) over the window's seconds times the int8 peak, in %."""


def read(rec):
    ops = rec["ops"]["decode"] + rec["ops"]["prefill"]
    return 100.0 * ops / (rec["trace"]["window_s"] * rec["peaks"]["int8_ops"])
