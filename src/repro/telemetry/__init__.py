"""repro.telemetry — spans, metrics and trace export for the serving stack.

The package is a sensor layer over :class:`repro.serve.engine.ServeEngine`
with two contracts, both enforced by ``tests/test_telemetry.py``:

* **zero-cost-when-off** — a ``telemetry=None`` engine (the default) runs
  the decode hot loop with ZERO additional host syncs, allocations, or
  hook calls (the module-level :data:`HOOK_CALLS` spy counts every hook
  entry, and the engine never calls ``jax.block_until_ready``);
* **bitwise stability when on** — every hook observes after the fact;
  enabling telemetry (or a ``jax.profiler`` trace) leaves every stream
  token-identical.

Composition (one object, three concerns):

* :class:`~repro.telemetry.metrics.MetricsRegistry` — typed counters /
  gauges / fixed-bucket histograms, auto-twinned with ``EngineStats``;
* :class:`~repro.telemetry.trace.Tracer` — request-lifecycle tracks and
  engine instants exported as Chrome trace-event JSON (Perfetto-loadable);
* exporters in :mod:`repro.telemetry.export` — Prometheus text, JSON
  snapshot, and the consolidated serving report.

Device time lives in a ``jax.profiler`` trace instead: the serving
programs' ops carry the model's layer scopes and the engine's host phases
are ``serve.*`` spans on the same clock; :mod:`repro.telemetry.xplane`
reads such a trace back (docs/observability.md).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from repro.telemetry.export import (parse_prometheus, serve_report, to_json,
                                    to_prometheus, write_json)
from repro.telemetry.metrics import (SECONDS_BUCKETS, TICK_BUCKETS, Counter,
                                     Gauge, Histogram, Metric,
                                     MetricsRegistry, format_group_layout,
                                     slot_utilization, spec_acceptance_rate,
                                     sync_engine_stats)
from repro.telemetry.trace import ENGINE_TRACK, PID, Tracer

__all__ = ["Telemetry", "HOOK_CALLS", "Counter", "Gauge", "Histogram",
           "Metric", "MetricsRegistry", "Tracer", "TICK_BUCKETS",
           "SECONDS_BUCKETS", "ENGINE_TRACK", "PID", "format_group_layout",
           "sync_engine_stats", "slot_utilization", "spec_acceptance_rate",
           "to_prometheus", "parse_prometheus", "to_json", "write_json",
           "serve_report"]

# Spy counter: EVERY Telemetry hook entry bumps it.  The zero-cost-when-off
# test drains a telemetry-None engine and asserts this never moved — the
# cheapest possible proof that the hot loop took no observability branches.
HOOK_CALLS = 0


def _bump() -> None:
    global HOOK_CALLS
    HOOK_CALLS += 1


@dataclasses.dataclass
class _RequestRecord:
    """Per-request latency bookkeeping (dual clock, host-side only)."""

    tier: Optional[str]
    deadline: Optional[float]
    submit_ticks: float
    submit_wall: float
    admitted: bool = False
    first_ticks: Optional[float] = None
    first_wall: Optional[float] = None
    last_ticks: float = 0.0
    last_wall: float = 0.0
    n_tokens: int = 0


class Telemetry:
    """The facade a :class:`~repro.serve.engine.ServeEngine` accepts as
    ``telemetry=``."""

    def __init__(self) -> None:
        self.registry = MetricsRegistry()
        self.tracer = Tracer()
        self._requests: Dict[int, _RequestRecord] = {}
        self._num_slots = 0
        r = self.registry
        self.queue_wait = r.histogram(
            "serve_queue_wait_ticks",
            "submit -> first admission, scheduler ticks", unit="ticks")
        self.ttft_ticks = r.histogram(
            "serve_ttft_ticks", "submit -> first token, scheduler ticks",
            unit="ticks")
        self.ttft_seconds = r.histogram(
            "serve_ttft_seconds", "submit -> first token, wall seconds",
            unit="s", buckets=SECONDS_BUCKETS)
        self.tpot_ticks = r.histogram(
            "serve_tpot_ticks", "mean ticks per output token after the "
            "first", unit="ticks")
        self.tpot_seconds = r.histogram(
            "serve_tpot_seconds", "mean wall seconds per output token "
            "after the first", unit="s", buckets=SECONDS_BUCKETS)
        self.deadline_misses = r.counter(
            "serve_deadline_misses",
            "requests that finished past submit + deadline (ticks)")
        r.gauge("serve_queue_depth", "requests waiting for a slot")
        r.gauge("serve_slot_utilization",
                "decode_slot_steps / (decode_steps * num_slots)")
        r.gauge("serve_spec_acceptance_rate", "spec_accepted / spec_drafted")

    # ------------------------------------------------------------ plumbing
    def wall(self) -> float:
        """Wall seconds since the tracer epoch (the span clock)."""
        return self.tracer.now()

    def attach_engine(self, *, num_slots: int) -> None:
        """Called by the engine at construction: slot count for the
        utilization denominator."""
        self._num_slots = num_slots

    # ----------------------------------------------------- request lifecycle
    def on_submit(self, handle: Any, *, ticks: float) -> None:
        _bump()
        req = handle.request
        self._requests[int(req.uid)] = _RequestRecord(
            tier=req.tier, deadline=req.deadline,
            submit_ticks=ticks, submit_wall=self.wall())
        self.tracer.request_phase(int(req.uid), "queued", ticks=ticks)

    def on_shed(self, handle: Any, *, ticks: float) -> None:
        _bump()
        uid = int(handle.request.uid)
        self.tracer.request_end(uid, "shed", ticks=ticks)
        self.tracer.engine_instant("shed", ticks=ticks, args={"uid": uid})
        self._requests.pop(uid, None)

    def on_admit(self, handle: Any, *, slot: int, ticks: float,
                 resumed: bool = False) -> None:
        _bump()
        uid = int(handle.request.uid)
        self.tracer.request_phase(uid, "running", ticks=ticks)
        if resumed:
            self.tracer.engine_instant("resume", ticks=ticks,
                                       args={"uid": uid})
        rec = self._requests.get(uid)
        if rec is not None and not rec.admitted:
            rec.admitted = True
            if not resumed:
                self.queue_wait.observe(ticks - rec.submit_ticks)

    def on_suspend(self, handle: Any, *, ticks: float) -> None:
        _bump()
        uid = int(handle.request.uid)
        self.tracer.request_phase(uid, "suspended", ticks=ticks)
        self.tracer.engine_instant("preempt", ticks=ticks,
                                   args={"uid": uid})

    def on_token(self, event: Any, *, ticks: float) -> None:
        _bump()
        uid = int(event.uid)
        rec = self._requests.get(uid)
        if rec is None:
            return
        now = self.wall()
        if rec.first_ticks is None:
            rec.first_ticks = ticks
            rec.first_wall = now
            self.ttft_ticks.observe(ticks - rec.submit_ticks)
            self.ttft_seconds.observe(now - rec.submit_wall)
        rec.n_tokens += 1
        rec.last_ticks = ticks
        rec.last_wall = now
        if event.final:
            n = max(rec.n_tokens - 1, 1)
            assert rec.first_wall is not None
            self.tpot_ticks.observe((rec.last_ticks - rec.first_ticks) / n)
            self.tpot_seconds.observe((rec.last_wall - rec.first_wall) / n)
            if rec.deadline is not None \
                    and ticks - rec.submit_ticks > float(rec.deadline):
                self.deadline_misses.inc()
            self.tracer.request_end(uid, "finished", ticks=ticks)
            self._requests.pop(uid, None)

    # ------------------------------------------------------ engine instants
    def on_migrate(self, *, uid: int, old_tier: Optional[str],
                   new_tier: str, kv: bool, ticks: float) -> None:
        _bump()
        self.tracer.engine_instant(
            "migrate", ticks=ticks,
            args={"uid": uid, "from": old_tier, "to": new_tier, "kv": kv})

    # ------------------------------------------------------------- syncing
    def sync_stats(self, stats: Any,
                   queue_depth: Optional[int] = None) -> None:
        """Mirror ``EngineStats`` into the registry and refresh the derived
        gauges.  The engine calls this after every state-changing op, so
        the fuzz harness can assert twin equality at any point."""
        _bump()
        sync_engine_stats(self.registry, stats)
        r = self.registry
        r.gauge("serve_slot_utilization").set(
            slot_utilization(stats, self._num_slots))
        r.gauge("serve_spec_acceptance_rate").set(spec_acceptance_rate(stats))
        if queue_depth is not None:
            r.gauge("serve_queue_depth").set(float(queue_depth))

    # ------------------------------------------------------------- exports
    def snapshot(self) -> Dict[str, Any]:
        """JSON-able dump of the metrics."""
        return to_json(self.registry)

    def prometheus(self) -> str:
        return to_prometheus(self.registry)

    def write_trace(self, path: str) -> None:
        self.tracer.write(path)
