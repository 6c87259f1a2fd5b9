"""From a ``jax.profiler`` trace to device busy time, program and kernel
times, and the idle gaps by what the host was doing.

``load`` reads the ``.xplane.pb`` the profiler writes and keeps three lists
of ``(name, start_s, dur_s)``: the device's operations (the ``XLA Ops`` line
of each TPU plane, by HLO instruction name without its numeric suffix, so
``grouped_dequant_matmul`` is every call of that Pallas kernel), its
programs (the ``XLA Modules`` line) and the host spans the benchmark opened
itself (names starting ``bench.``).  Loops (``while``) appear as events that
hold their body's events: they count toward busy time, whose union is
taken, but not among the top operations.  ``reduce``
works on those lists alone, so it is tested on hand-made ones.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Any, Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start (s), duration (s)
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
CONTAINERS = ("while", "conditional", "call")   # hold other ops' events


def short_name(op: str) -> str:
    """``%grouped_dequant_matmul.75 = bf16[16,12800]... custom-call(...)``
    -> ``grouped_dequant_matmul``: the HLO instruction's name without its
    ``%`` and its numeric suffix (the trace names an op by its whole HLO
    text)."""
    head = op.split(" = ", 1)[0].strip().lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def load(logdir: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        name = plane.name
        for line in plane.lines:
            if name.startswith("/device:TPU:") and line.name in (
                    "XLA Ops", "XLA Modules"):
                dest = ops if line.name == "XLA Ops" else modules
                dest.setdefault(name, []).extend(
                    (short_name(e.name), e.start_ns * 1e-9,
                     e.duration_ns * 1e-9) for e in line.events)
            elif name.startswith("/host:"):
                host.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"ops": ops, "modules": modules, "host": host}


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _clip(events: Sequence[Event], t0: float, t1: float) -> List[Event]:
    out = []
    for n, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((n, a, b - a))
    return out


def _attribute(spans: Sequence[Event], a: float, b: float,
               into: Dict[str, float], starts: Sequence[float]) -> None:
    """Add the gap ``[a, b]`` to ``into`` by the host span open over each
    part of it (the benchmark's spans follow one another; time under none
    of them is ``host.other``)."""
    j = max(0, bisect.bisect_right(starts, a) - 1)
    t = a
    while t < b:
        while j < len(spans) and spans[j][1] + spans[j][2] <= t:
            j += 1
        if j == len(spans) or spans[j][1] >= b:
            into["host.other"] += b - t
            return
        name, s, d = spans[j]
        if s > t:
            into["host.other"] += s - t
            t = s
        end = min(b, s + d)
        into[name] += end - t
        t = end


def reduce(raw: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """Busy and idle time of the traced window, averaged over the device
    planes; total time and count of each device operation and program; idle
    seconds by the benchmark span open during each part of each gap."""
    windows = [h for h in raw["host"] if h[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    _, t0, dur = windows[0]
    t1 = t0 + dur
    spans = sorted((h for h in raw["host"] if h[0] != WINDOW_SPAN),
                   key=lambda h: h[1])
    starts = [h[1] for h in spans]
    devices = sorted(raw["ops"])
    if not devices:
        raise ValueError("trace holds no device operations")
    busy_total = 0.0
    op_time: Dict[str, float] = collections.defaultdict(float)
    op_count: Dict[str, int] = collections.defaultdict(int)
    mod_time: Dict[str, float] = collections.defaultdict(float)
    mod_count: Dict[str, int] = collections.defaultdict(int)
    idle: Dict[str, float] = collections.defaultdict(float)
    for dev in devices:
        ops = _clip(raw["ops"][dev], t0, t1)
        busy = union((s, s + d) for _, s, d in ops)
        busy_total += sum(b - a for a, b in busy)
        for n, _, d in ops:
            op_time[n] += d
            op_count[n] += 1
        for n, _, d in _clip(raw["modules"].get(dev, []), t0, t1):
            mod_time[n] += d
            mod_count[n] += 1
        edges = [t0] + [x for ab in busy for x in ab] + [t1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                _attribute(spans, a, b, idle, starts)
    n_dev = len(devices)
    return {
        "window_s": t1 - t0,
        "busy_s": busy_total / n_dev,
        "devices": n_dev,
        "op_time": dict(op_time), "op_count": dict(op_count),
        "module_time": dict(mod_time), "module_count": dict(mod_count),
        "idle_by_span": dict(idle),
        "device_ops": sorted(([n, t] for n, t in op_time.items()
                              if n not in CONTAINERS),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([n, t / n_dev] for n, t in idle.items()),
                            key=lambda x: -x[1])[:top],
    }


def matching(table: Dict[str, float], patterns: Sequence[str]) -> float:
    """Sum of the entries whose name contains any of ``patterns``."""
    return sum(v for k, v in table.items() if any(p in k for p in patterns))
