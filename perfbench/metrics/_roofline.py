"""Shared arithmetic of the kernel roofline readers (not a metric)."""
import sys


def share(rec, kernel):
    """Least time of the kernel's calls (from shapes) over its device time
    in the trace, in %; None when the trace holds none of its events or
    not the number of calls the window dispatched."""
    work = rec["work"]
    calls = work.calls.get(kernel, 0)
    pats = rec["kernels"][kernel]
    t = sum(rec["trace"]["op_time"].get(p, 0.0) for p in pats)
    n = sum(rec["trace"]["op_count"].get(p, 0) for p in pats)
    if not calls or t <= 0:
        return None
    if n != calls:
        print(f"{kernel}: {n} trace events, {calls} calls dispatched; "
              "roofline not read", file=sys.stderr)
        return None
    return 100.0 * work.least_s[kernel] / t
