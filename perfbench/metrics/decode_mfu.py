"""Model operations of the decoded tokens over the decode programs' device
time times the int8 peak, in %: the whole decode step's share of the chip."""
from perfbench.metrics import _mfu


def read(rec):
    return _mfu.over_program(rec, "decode")
