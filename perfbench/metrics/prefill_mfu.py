"""Model operations of the prefilled prompts over the prefill programs'
device time times the int8 peak, in %: the whole prefill's share of the
chip."""
from perfbench.metrics import _mfu


def read(rec):
    return _mfu.over_program(rec, "prefill")
