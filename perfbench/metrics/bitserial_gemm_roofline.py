"""Roofline share of the single-width bit-serial plane GEMM (prefill, and
decode of one-tier batches): least time from its calls' shapes over its
device time, in %."""
from perfbench.metrics import _roofline


def read(rec):
    return _roofline.share(rec, "bitserial")
