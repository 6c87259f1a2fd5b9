"""Shared arithmetic of the kernel roofline readers (not a metric)."""
import sys


def share(rec, kernel):
    """Least time of the kernel's calls (from shapes) over its device time
    in the trace, in %; None when the trace holds none of its events or
    not the number of calls the window dispatched.  Its calls are those
    under its own key and, for routed experts, under ``<kernel>.expert``:
    the trace names both by the kernel they run on."""
    work = rec["work"]
    keys = (kernel, kernel + ".expert")
    calls = sum(work.calls.get(k, 0) for k in keys)
    least_s = sum(work.least_s.get(k, 0.0) for k in keys)
    pats = rec["kernels"][kernel]
    t = sum(rec["trace"]["op_time"].get(p, 0.0) for p in pats)
    n = sum(rec["trace"]["op_count"].get(p, 0) for p in pats)
    if not calls or t <= 0:
        return None
    if n != calls:
        print(f"{kernel}: {n} trace events, {calls} calls dispatched; "
              "roofline not read", file=sys.stderr)
        return None
    return 100.0 * least_s / t
