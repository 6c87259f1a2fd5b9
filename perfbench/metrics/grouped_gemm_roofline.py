"""Roofline share of the grouped (mixed-tier) plane GEMM: least time from
its calls' shapes at the widest tier's bits over its device time, in %."""
from perfbench.metrics import _roofline


def read(rec):
    return _roofline.share(rec, "grouped")
