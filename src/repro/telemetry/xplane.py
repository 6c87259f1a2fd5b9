"""Read a ``jax.profiler`` trace of the serving engine back: device time by
program and model layer, the engine's host phases, and the device's idle
time by the phase the host was in.

The serving programs name their layers with ``jax.named_scope``
(:data:`LAYER_SCOPES`); the scope path lands in each HLO instruction's
``op_name`` metadata.  A device op belongs to the outermost layer scope in
its path, or to :data:`OTHER` (norms, RoPE, residual adds, loop carries,
copies XLA inserts).  The trace keeps every program's HLO in its
``/host:metadata`` plane; :func:`load` finds each device op's program and
instruction there (on a TPU from the ``XLA Modules`` event that holds the
op and the op's HLO text, on the CPU from its ``program_id`` and
``hlo_op`` stats) and takes its scope from the instruction's metadata.
The engine's host phases are ``jax.profiler.TraceAnnotation`` spans named
``serve.*`` (``repro.serve.engine``), on the same clock as the device ops.

``load`` turns the ``.xplane.pb`` file into plain lists; ``reduce`` works
on those lists alone, so it is tested on hand-made ones.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

__all__ = ["LAYER_SCOPES", "OTHER", "SPAN_PREFIX", "Op", "Span", "scope_of",
           "load", "reduce", "format_summary"]

# The model's layer scopes, outermost first where they nest (the untied
# head's ``linear`` call sits inside ``lm_head``).
LAYER_SCOPES = ("linear", "kv_write", "attention", "lm_head", "sample",
                "slot_io")
OTHER = "other"
SPAN_PREFIX = "serve."
STEP_SPAN = "serve.step"
WAIT_PREFIX = "serve.wait_"
# Ops that hold other ops' events (loop bodies, branches): they count
# toward busy time but not toward any scope.
CONTAINERS = ("while", "conditional", "call")
NO_SPAN = "none"
# Spans that touch within this (seconds) follow one another: trace times
# are whole nanoseconds, sums of them in seconds are not exact.
EPS = 1e-9


@dataclasses.dataclass(frozen=True)
class Op:
    """One device op: its device plane, HLO module (program), layer scope,
    start and duration in seconds."""
    device: str
    module: str
    scope: str
    start: float
    dur: float
    name: str = ""


@dataclasses.dataclass(frozen=True)
class Span:
    """One host span: name, start and duration in seconds, the host line
    (thread) it ran on, and its arguments."""
    name: str
    start: float
    dur: float
    line: str = ""
    args: Tuple[Tuple[str, Any], ...] = ()

    @property
    def end(self) -> float:
        return self.start + self.dur


def scope_of(op_name: str) -> str:
    """The outermost layer scope in an ``op_name`` path, else ``other``:
    ``jit(decode_chunk_fn)/while/body/lm_head/linear/dot_general`` ->
    ``lm_head``."""
    for part in op_name.split("/"):
        if part in LAYER_SCOPES:
            return part
    return OTHER


def _base(name: str) -> str:
    """``fusion.12`` -> ``fusion``; ``while`` -> ``while``."""
    head, _, suffix = name.rpartition(".")
    return head if head and suffix.isdigit() else name


def _instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[8]{0} fusion(...)`` -> ``fusion.12`` (the trace
    names a TPU op by its HLO text); a bare name passes through."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


# ------------------------------------------------- protobuf wire reading
# The HLO of each program sits in the metadata plane as event metadata
# that no event refers to, which ``jax.profiler.ProfileData`` does not
# expose; these few lines read the XSpace wire format for it.
def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes) -> Iterator[Tuple[int, Any]]:
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        v: Any
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield num, v


def _sub(b: bytes, num: int) -> List[bytes]:
    return [v for f, v in _fields(b) if f == num]


def _str(b: bytes, num: int) -> str:
    vals = _sub(b, num)
    return bytes(vals[0]).decode(errors="replace") if vals else ""


def hlo_op_names(module_proto: bytes) -> Tuple[str, Dict[str, str]]:
    """An ``HloModuleProto`` -> (module name, {instruction: op_name})."""
    ops: Dict[str, str] = {}
    for comp in _sub(module_proto, 3):                 # computations
        for inst in _sub(comp, 2):                     # instructions
            meta = _sub(inst, 7)                       # OpMetadata
            ops[_str(inst, 1)] = _str(meta[0], 2) if meta else ""
    return _str(module_proto, 1), ops


def program_scopes(xspace: bytes) -> Dict[int, Tuple[str, Dict[str, str]]]:
    """program id -> (module name, {instruction: layer scope}) from the
    HLO protos in the trace's ``/host:metadata`` plane."""
    out: Dict[int, Tuple[str, Dict[str, str]]] = {}
    for plane in _sub(xspace, 1):
        if _str(plane, 2) != "/host:metadata":
            continue
        for entry in _sub(plane, 4):                   # event_metadata map
            pid_l, meta_l = _sub(entry, 1), _sub(entry, 2)
            if not pid_l or not meta_l:
                continue
            for stat in _sub(meta_l[0], 5):
                for blob in _sub(stat, 6):             # bytes: HloProto
                    for mod in _sub(blob, 1):          # hlo_module
                        name, ops = hlo_op_names(mod)
                        out[int(pid_l[0])] = (name, {
                            k: scope_of(v) for k, v in ops.items()})
    return out


# ------------------------------------------------------------------ load
def _newest(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def _program_id(module_event: str) -> int:
    """``jit_decode_chunk_fn(42)`` -> 42 (the program id the runtime puts
    in a program's event name), else -1."""
    head, _, tail = module_event.rstrip(")").rpartition("(")
    return int(tail) if head and tail.isdigit() else -1


def load(logdir: str) -> Dict[str, Any]:
    """The newest trace under ``logdir`` as ``{"ops": [Op], "modules":
    [Op], "spans": [Span]}``: every device op with its layer scope, every
    program run (the ``XLA Modules`` line, scope empty) and every
    ``serve.*`` host span.  Device ops are the ``XLA Ops`` lines of the
    accelerator planes; on the CPU backend, the host events that carry an
    ``hlo_op`` stat.  An op's program is its ``program_id`` stat, else the
    program run that holds it on its plane."""
    from jax.profiler import ProfileData
    path = _newest(logdir)
    with open(path, "rb") as f:
        raw = f.read()
    programs = program_scopes(raw)
    ops: List[Op] = []
    modules: List[Op] = []
    spans: List[Span] = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        pname = plane.name
        device = pname.startswith("/device:")
        lines = list(plane.lines)
        runs = sorted((e.start_ns * 1e-9, e.duration_ns * 1e-9, e.name)
                      for line in lines if device
                      and line.name == "XLA Modules" for e in line.events)
        modules += [Op(pname, n.split("(", 1)[0], "", t, d, n)
                    for t, d, n in runs]
        run_starts = [t for t, _, _ in runs]
        for line in lines:
            if device and line.name != "XLA Ops":
                continue
            for e in line.events:
                start, dur = e.start_ns * 1e-9, e.duration_ns * 1e-9
                if not device and e.name.startswith(SPAN_PREFIX):
                    spans.append(Span(e.name, start, dur, line.name,
                                      tuple(e.stats)))
                    continue
                stats = dict(e.stats)
                if not device and "hlo_op" not in stats:
                    continue
                inst = str(stats.get("hlo_op") or _instruction(e.name))
                pid = stats.get("program_id")
                if pid is None:
                    k = bisect.bisect_right(run_starts, start) - 1
                    pid = _program_id(runs[k][2]) if k >= 0 else -1
                module, scopes = programs.get(int(pid), ("", {}))
                ops.append(Op(pname, str(stats.get("hlo_module") or module),
                              scopes.get(inst, OTHER), start, dur, inst))
    return {"ops": ops, "modules": modules, "spans": spans}


# ---------------------------------------------------------------- reduce
def _union(intervals: Sequence[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _nest(spans: Sequence[Span]) -> List[Tuple[Span, Optional[Span]]]:
    """Each span with its parent (the innermost span of the same line that
    holds it), in start order."""
    out: List[Tuple[Span, Optional[Span]]] = []
    stacks: Dict[str, List[Span]] = collections.defaultdict(list)
    for s in sorted(spans, key=lambda s: (s.start, -s.dur)):
        stack = stacks[s.line]
        while stack and stack[-1].end <= s.start + EPS:
            stack.pop()
        out.append((s, stack[-1] if stack else None))
        stack.append(s)
    return out


def _innermost(spans: Sequence[Span]) -> List[Tuple[str, float, float]]:
    """Cut nested spans (of one thread) into back-to-back segments
    ``(name, start, end)``, each named by the innermost span open over
    it."""
    segs: List[Tuple[str, float, float]] = []
    stack: List[Span] = []
    t = 0.0

    def close_until(limit: float) -> float:
        tt = t
        while stack and stack[-1].end <= limit + EPS:
            top = stack.pop()
            if top.end > tt:
                segs.append((top.name, tt, top.end))
                tt = top.end
        return tt

    for s in sorted(spans, key=lambda s: (s.start, -s.dur)):
        t = close_until(s.start)
        if stack and s.start > t:
            segs.append((stack[-1].name, t, s.start))
        stack.append(s)
        t = s.start
    close_until(float("inf"))
    return segs


def _attribute(segs: Sequence[Tuple[str, float, float]],
               ends: Sequence[float], a: float, b: float,
               into: Dict[str, float]) -> None:
    """Add the gap ``[a, b]`` to ``into`` by the segment over each part of
    it (time under none is ``none``); ``ends`` are the segments' ends."""
    t = a
    for name, s, e in segs[bisect.bisect_right(ends, a):]:
        if s >= b:
            break
        if s > t:
            into[NO_SPAN] += s - t
        end = min(b, e)
        into[name] += end - max(t, s)
        t = end
    if t < b:
        into[NO_SPAN] += b - t


def reduce(trace: Dict[str, Any], window: Optional[Tuple[float, float]] = None
           ) -> Dict[str, Any]:
    """Sum a loaded trace over ``window`` (default: the whole trace).

    * ``busy_s`` — union of device-op intervals, averaged over devices;
    * ``module_time`` — seconds by program (``XLA Modules`` line);
    * ``scope_time`` — seconds by ``<module>/<scope>``, containers left out;
    * ``span_time`` — ``{name: {total_s, self_s, count}}`` of every
      ``serve.*`` span wholly in the window (self time leaves out nested
      spans);
    * ``round_host_s`` — mean over ``serve.step`` spans of their duration
      less the ``serve.wait_*`` spans inside them: the part of a round in
      which the host, not the device, sets the pace;
    * ``idle_by_span`` — idle device seconds by the innermost ``serve.*``
      span open over each part of each gap, averaged over devices."""
    ops: List[Op] = trace["ops"]
    spans: List[Span] = trace["spans"]
    if window is None:
        ends = [(o.start, o.start + o.dur) for o in ops] + \
            [(s.start, s.end) for s in spans]
        if not ends:
            raise ValueError("trace holds no device op and no span")
        window = (min(a for a, _ in ends), max(b for _, b in ends))
    t0, t1 = window
    devices = sorted({o.device for o in ops})
    scope_time: Dict[str, float] = collections.defaultdict(float)
    module_time: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    inside = [s for s in spans if s.start >= t0 and s.end <= t1]
    segs = _innermost(inside)
    seg_ends = [e for _, _, e in segs]
    busy_total = 0.0
    for dev in devices:
        clipped = [(o, max(o.start, t0), min(o.start + o.dur, t1))
                   for o in ops if o.device == dev]
        clipped = [(o, a, b) for o, a, b in clipped if b > a]
        busy = _union([(a, b) for _, a, b in clipped])
        busy_total += sum(b - a for a, b in busy)
        for o, a, b in clipped:
            if _base(o.name) not in CONTAINERS:
                scope_time[f"{o.module}/{o.scope}"] += b - a
        edges = [t0] + [x for ab in busy for x in ab] + [t1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                _attribute(segs, seg_ends, a, b, idle)
    for m in trace.get("modules", []):
        a, b = max(m.start, t0), min(m.start + m.dur, t1)
        if b > a:
            module_time[m.module] += b - a
    span_time: Dict[str, Dict[str, float]] = {}
    parents: Dict[Span, Optional[Span]] = {}
    for s, parent in _nest(inside):
        parents[s] = parent
        rec = span_time.setdefault(s.name, {"total_s": 0.0, "self_s": 0.0,
                                            "count": 0})
        rec["total_s"] += s.dur
        rec["self_s"] += s.dur
        rec["count"] += 1
        if parent is not None:
            span_time[parent.name]["self_s"] -= s.dur
    steps = [s for s in inside if s.name == STEP_SPAN]
    waits: Dict[Span, float] = collections.defaultdict(float)
    for s in inside:
        if s.name.startswith(WAIT_PREFIX):
            up = parents[s]
            while up is not None and up.name != STEP_SPAN:
                up = parents[up]
            if up is not None:
                waits[up] += s.dur
    n_dev = max(len(devices), 1)
    return {
        "window_s": t1 - t0,
        "busy_s": busy_total / n_dev,
        "devices": len(devices),
        "module_time": dict(module_time),
        "scope_time": dict(scope_time),
        "span_time": span_time,
        "round_host_s": (sum(s.dur - waits[s] for s in steps) / len(steps)
                         if steps else None),
        "idle_by_span": {k: v / n_dev for k, v in idle.items()},
    }


def format_summary(summary: Dict[str, Any], top: int = 12) -> str:
    """A few lines for a terminal: device time by program and scope, the
    engine's phases, and idle time by phase."""
    w = summary["window_s"]
    lines = [f"window {w:.3f}s, device busy {summary['busy_s']:.3f}s on "
             f"{summary['devices']} device(s)"]
    for k, v in sorted(summary["scope_time"].items(), key=lambda x: -x[1])[
            :top]:
        lines.append(f"  device {k}: {v * 1e3:.3f} ms")
    for k, r in sorted(summary["span_time"].items(),
                       key=lambda x: -x[1]["total_s"]):
        lines.append(f"  span {k}: {r['count']:.0f} x, total "
                     f"{r['total_s'] * 1e3:.3f} ms, self "
                     f"{r['self_s'] * 1e3:.3f} ms")
    if summary["round_host_s"] is not None:
        lines.append(f"  host time per round (serve.step less its waits): "
                     f"{summary['round_host_s'] * 1e3:.3f} ms")
    for k, v in sorted(summary["idle_by_span"].items(), key=lambda x: -x[1]):
        lines.append(f"  device idle under {k}: {v * 1e3:.3f} ms")
    return "\n".join(lines)
