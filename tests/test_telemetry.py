"""repro.telemetry: the two contracts plus the exporter schemas.

* zero-cost-when-off — a ``telemetry=None`` engine drains a full mixed-tier
  stream without one hook call (module-level ``HOOK_CALLS`` spy) and
  without one host fence (``jax.block_until_ready`` is monkeypatched to
  raise for the whole drain);
* bitwise stability when on — telemetry, with a ``jax.profiler`` trace
  recording the engine's ``serve.*`` spans, leaves every stream
  token-identical, for mixed tiers, speculative decoding, and a 2-device
  mesh engine (subprocess);
* exporters — the Chrome trace validates against the trace-event schema
  (required keys, monotone ``ts`` per track) and the Prometheus text
  round-trips bit-exactly through the companion parser.
"""
import json

import jax
import numpy as np
import pytest

import repro.telemetry as telemetry_mod
from repro.configs import reduced_config
from repro.core.policy import uniform_schedule
from repro.models.layers import Runtime
from repro.models.transformer import LM
from repro.serve import Request, ServeEngine, SpecConfig
from repro.serve.engine import EngineStats
from repro.telemetry import (SECONDS_BUCKETS, Histogram, MetricsRegistry,
                             Telemetry, Tracer, format_group_layout,
                             parse_prometheus, serve_report,
                             sync_engine_stats, to_prometheus, xplane)
from test_sharded_serving import run_subprocess

TIERS = {"8/8": (8, 8), "4/4": (4, 4), "2/2": (2, 2)}


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("granite-3-8b")
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = uniform_schedule(TIERS, kv_tiers={"8/8": None, "4/4": 8,
                                              "2/2": 4})
    rt = Runtime(policy=sched.policy_for(), mode="serve", moe_dropless=True,
                 schedule=sched)
    return cfg, model, params, rt


def _requests(cfg, n=6, seed=13, **extra):
    rng = np.random.default_rng(seed)
    names = list(TIERS)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               size=3 + i % 4),
                    max_new_tokens=5 + i % 3, tier=names[i % 3], **extra)
            for i in range(n)]


# ------------------------------------------------------------- primitives
def test_histogram_quantiles_interpolate():
    h = Histogram("h", "", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0):
        h.observe(v)
    assert h.count == 4 and h.sum == pytest.approx(6.5)
    assert h.mean() == pytest.approx(6.5 / 4)
    # counts: [1 (<=1), 2 (<=2), 1 (<=4), 0 (+Inf)]
    assert h.counts == [1, 2, 1, 0]
    assert h.quantile(0.0) == 0.0
    # target 2.0 lands in the (1, 2] bucket: 1 + (2-1)/2 * (2-1) = 1.5
    assert h.quantile(0.5) == pytest.approx(1.5)
    assert h.quantile(1.0) == pytest.approx(4.0)
    h.observe(100.0)                      # overflow bucket degenerates
    assert h.quantile(1.0) == pytest.approx(4.0)
    assert Histogram("e", "").quantile(0.99) == 0.0


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError, match="ascending"):
        Histogram("h", "", buckets=(2.0, 1.0))
    with pytest.raises(ValueError, match="Inf"):
        Histogram("h", "", buckets=(1.0, float("inf")))
    with pytest.raises(ValueError, match="outside"):
        Histogram("h", "", buckets=(1.0,)).quantile(1.5)


def test_registry_idempotent_and_kind_clash():
    r = MetricsRegistry()
    c = r.counter("serve_x", "first")
    assert r.counter("serve_x", "second") is c
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("serve_x")
    c.inc(2.0)
    assert r.value("serve_x") == 2.0
    with pytest.raises(ValueError, match="negative"):
        c.inc(-1.0)
    r.histogram("serve_h", "")
    with pytest.raises(TypeError, match="histogram"):
        r.value("serve_h")
    g = r.gauge("serve_by_tier", labels=("tier",))
    g.set(3.0, tier="4/4")
    assert r.value("serve_by_tier", tier="4/4") == 3.0
    assert r.value("serve_by_tier", tier="2/2") == 0.0
    with pytest.raises(ValueError, match="expected labels"):
        g.set(1.0, wrong="x")
    assert r.value("never_registered") == 0.0


def test_sync_engine_stats_twins():
    stats = EngineStats()
    stats.prefills = 3
    stats.decode_steps = 17
    stats.decode_steps_by_tier["4/4"] = 9
    stats.tokens_by_tier["2/2"] = 5
    stats.decode_dispatches[(("8/8", 2), ("4/4", 1))] = 8
    r = MetricsRegistry()
    sync_engine_stats(r, stats)
    assert r.value("serve_prefills") == 3.0
    assert r.value("serve_decode_steps") == 17.0
    assert r.value("serve_decode_steps_by_tier", tier="4/4") == 9.0
    assert r.value("serve_tokens_by_tier", tier="2/2") == 5.0
    assert r.value("serve_decode_dispatches", layout="8/8x2+4/4x1") == 8.0
    # re-sync after mutation: twins follow, nothing double-counts
    stats.decode_steps = 18
    sync_engine_stats(r, stats)
    assert r.value("serve_decode_steps") == 18.0


def test_format_group_layout():
    assert format_group_layout((("8/8", 2), ("4/4", 1))) == "8/8x2+4/4x1"
    assert format_group_layout(()) == ""


# -------------------------------------------------------------- exporters
def test_prometheus_roundtrip_bit_exact():
    r = MetricsRegistry()
    r.counter("serve_total", "a\ncounter").inc(0.1 + 0.2)  # non-terminating
    r.gauge("serve_ratio").set(1e-17)
    lab = r.counter("serve_by_tier", labels=("tier",))
    lab.inc(3.0, tier='we"ird\\tier\n')                    # escaping
    h = r.histogram("serve_lat", "latency", buckets=(1.0, 8.0))
    for v in (0.5, 4.0, 99.0):
        h.observe(v)
    text = to_prometheus(r)
    assert "# TYPE serve_lat histogram" in text
    parsed = parse_prometheus(text)
    assert parsed["serve_total"][()] == 0.1 + 0.2          # bit-exact
    assert parsed["serve_ratio"][()] == 1e-17
    assert parsed["serve_by_tier"][(("tier", 'we"ird\\tier\n'),)] == 3.0
    buckets = parsed["serve_lat_bucket"]
    assert buckets[(("le", "1.0"),)] == 1.0                # cumulative
    assert buckets[(("le", "8.0"),)] == 2.0
    assert buckets[(("le", "+Inf"),)] == 3.0
    assert parsed["serve_lat_count"][()] == 3.0
    assert parsed["serve_lat_sum"][()] == 103.5
    with pytest.raises(ValueError, match="unparseable"):
        parse_prometheus("this is not a metric line")


def test_tracer_schema_and_monotone_tracks(tmp_path):
    tr = Tracer()
    tr.request_phase(0, "queued", ticks=0.0)
    tr.request_phase(1, "queued", ticks=0.0)
    tr.request_phase(0, "running", ticks=0.0)
    tr.engine_instant("migrate", ticks=2.0,
                      args={"uid": 0, "from": "8/8", "to": "4/4"})
    tr.engine_instant("preempt", ticks=4.0, args={"uid": 0})
    tr.request_phase(0, "suspended", ticks=4.0)
    tr.request_end(0, "finished", ticks=8.0)
    tr.request_end(1, "shed", ticks=8.0)
    path = tmp_path / "trace.json"
    tr.write(str(path))
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert events, "trace must not be empty"
    for ev in events:
        assert {"name", "ph", "pid", "tid"} <= set(ev), ev
        assert ev["pid"] == 1
        assert ev["ph"] in ("X", "i", "M")
        if ev["ph"] != "M":               # metadata events carry no ts
            assert "ts" in ev, ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        if ev["ph"] == "i":
            assert ev["s"] == "t"
    body = [ev for ev in events if ev["ph"] != "M"]
    by_track = {}
    for ev in body:
        by_track.setdefault(ev["tid"], []).append(ev["ts"])
    assert set(by_track) == {0, 1, 2}      # engine + one track per uid
    for tid, stamps in by_track.items():
        assert stamps == sorted(stamps), f"track {tid} ts not monotone"
    names = {(ev["tid"], ev["name"]) for ev in body}
    for want in [(0, "migrate"), (0, "preempt"),
                 (1, "queued"), (1, "running"), (1, "suspended"),
                 (1, "finished"), (2, "queued"), (2, "shed")]:
        assert want in names, f"missing event {want}"


# ------------------------------------------------------ engine contracts
def test_zero_cost_when_off(setup, monkeypatch):
    """A telemetry-less engine takes no hooks and no host fences."""
    cfg, model, params, rt = setup

    def forbidden(*a, **k):
        raise AssertionError("engine fenced the device without telemetry")

    monkeypatch.setattr(jax, "block_until_ready", forbidden)
    eng = ServeEngine(model, params, rt, max_batch=3, max_len=64,
                      decode_chunk=4)
    before = telemetry_mod.HOOK_CALLS
    out = eng.run(_requests(cfg))
    assert sum(len(v) for v in out.values()) > 0
    assert telemetry_mod.HOOK_CALLS == before, \
        "telemetry-off engine called observability hooks"


def test_token_identity_mixed_tiers(setup, tmp_path):
    """Telemetry under an active ``jax.profiler`` trace changes no tokens,
    the EngineStats twins agree, latency histograms cover every request,
    the trace holds one engine span per dispatch, and the report +
    exporters render from the same registry."""
    cfg, model, params, rt = setup
    off = ServeEngine(model, params, rt, max_batch=3, max_len=64,
                      decode_chunk=4)
    got_off = off.run(_requests(cfg))

    tele = Telemetry()
    on = ServeEngine(model, off.params, rt, max_batch=3, max_len=64,
                     decode_chunk=4, telemetry=tele)
    logdir = tmp_path / "profile"
    jax.profiler.start_trace(str(logdir))
    try:
        got_on = on.run(_requests(cfg))
    finally:
        jax.profiler.stop_trace()
    assert got_on == got_off

    reg = tele.registry
    import dataclasses
    for f in dataclasses.fields(on.stats):
        v = getattr(on.stats, f.name)
        if isinstance(v, int):
            assert reg.value("serve_" + f.name) == float(v), f.name
    for tier, n in on.stats.decode_steps_by_tier.items():
        assert reg.value("serve_decode_steps_by_tier",
                         tier=tier) == float(n)
    n = len(got_on)
    assert reg.get("serve_queue_wait_ticks").count == n
    assert reg.get("serve_ttft_ticks").count == n
    assert reg.get("serve_tpot_ticks").count == n
    assert reg.get("serve_ttft_seconds").count == n
    assert 0.0 < reg.value("serve_slot_utilization") <= 1.0
    assert reg.get("serve_modeled_cycle_utilization") is None

    spans = xplane.reduce(xplane.load(str(logdir)))["span_time"]
    assert spans["serve.prefill"]["count"] == on.stats.prefills
    assert spans["serve.decode"]["count"] == on.stats.decode_chunks
    assert spans["serve.submit"]["count"] == n
    assert spans["serve.decode"]["total_s"] > 0.0

    # every export path renders off the same state
    report = serve_report(reg, tiers=list(TIERS))
    assert "slot_util=" in report and "ttft" in report
    parsed = parse_prometheus(tele.prometheus())
    assert parsed["serve_decode_steps"][()] == float(on.stats.decode_steps)
    path = tmp_path / "trace.json"
    tele.write_trace(str(path))
    events = json.loads(path.read_text())["traceEvents"]
    tracks = {ev["tid"] for ev in events if ev["ph"] != "M"}
    assert tracks == {uid + 1 for uid in got_on}    # no engine instants
    snap = tele.snapshot()
    assert snap["metrics"]["serve_ttft_ticks"]["count"] == n
    assert set(snap) == {"metrics"}


def test_token_identity_speculative(setup):
    """Telemetry through the speculative engine: token-identical, spec
    counters mirrored, acceptance-rate gauge consistent."""
    cfg, model, params, rt0 = setup
    sched = uniform_schedule(TIERS, kv_tiers={"8/8": 8, "4/4": 8,
                                              "2/2": 8})
    rt = Runtime(policy=sched.policy_for(), mode="serve", moe_dropless=True,
                 schedule=sched)
    reqs = dict(n=4, seed=7, spec=SpecConfig(draft_tier="2/2", k=2))
    off = ServeEngine(model, params, rt, max_batch=2, max_len=64,
                      decode_chunk=2)
    got_off = off.run(_requests(cfg, **reqs))
    tele = Telemetry()
    on = ServeEngine(model, off.params, rt, max_batch=2, max_len=64,
                     decode_chunk=2, telemetry=tele)
    got_on = on.run(_requests(cfg, **reqs))
    assert got_on == got_off
    assert on.stats.spec_rounds > 0
    reg = tele.registry
    assert reg.value("serve_spec_rounds") == float(on.stats.spec_rounds)
    assert reg.value("serve_spec_accepted") == float(on.stats.spec_accepted)
    rate = reg.value("serve_spec_acceptance_rate")
    assert rate == pytest.approx(
        on.stats.spec_accepted / on.stats.spec_drafted)
    assert "speculate: rounds=" in serve_report(reg, speculate=True)


def test_deadline_miss_counter(setup):
    """serve_deadline_misses is telemetry-owned: an impossible deadline
    counts once, a generous one doesn't."""
    cfg, model, params, rt = setup
    tele = Telemetry()
    eng = ServeEngine(model, params, rt, max_batch=2, max_len=64,
                      decode_chunk=4, telemetry=tele)
    reqs = _requests(cfg, n=2)
    reqs[0].deadline = 0.5          # < 1 tick: cannot be met
    reqs[1].deadline = 1e6
    eng.run(reqs)
    assert tele.registry.value("serve_deadline_misses") == 1.0


def test_mesh_token_identity_with_telemetry():
    """2-device mesh engine with telemetry, under an active profiler trace,
    == unsharded engine without, token for token."""
    out = run_subprocess("""
        import tempfile
        import jax, numpy as np
        from repro.configs import reduced_config
        from repro.core.policy import uniform_schedule
        from repro.launch.mesh import make_serve_mesh
        from repro.models.layers import Runtime
        from repro.models.transformer import LM
        from repro.serve import Request, ServeEngine
        from repro.telemetry import Telemetry, xplane

        cfg = reduced_config("qwen3-8b")
        model = LM(cfg)
        params = model.init(jax.random.PRNGKey(0))
        sched = uniform_schedule(
            {"8/8": (8, 8), "4/4": (4, 4), "2/2": (2, 2)},
            kv_tiers={"8/8": None, "4/4": 8, "2/2": 4})
        rt = Runtime(policy=sched.policy_for(), mode="serve",
                     schedule=sched)
        tiers = ["8/8", "4/4", "2/2"]

        def serve(mesh, telemetry):
            rng = np.random.default_rng(0)
            eng = ServeEngine(model, params, rt, max_batch=3, max_len=64,
                              decode_chunk=4, mesh=mesh,
                              telemetry=telemetry)
            reqs = [Request(uid=i,
                            prompt=rng.integers(0, cfg.vocab_size, size=4),
                            max_new_tokens=8, tier=tiers[i % 3])
                    for i in range(4)]
            return eng.run(reqs), eng

        ref, _ = serve(None, None)
        tele = Telemetry()
        logdir = tempfile.mkdtemp()
        jax.profiler.start_trace(logdir)
        try:
            tp2, eng2 = serve(make_serve_mesh(2), tele)
        finally:
            jax.profiler.stop_trace()
        assert eng2._tp is not None
        assert ref == tp2, (ref, tp2)
        assert tele.registry.value("serve_decode_steps") \\
            == float(eng2.stats.decode_steps)
        spans = xplane.reduce(xplane.load(logdir))["span_time"]
        assert spans["serve.decode"]["count"] == eng2.stats.decode_chunks
        print("TELEMETRY_TP_OK", sum(len(v) for v in ref.values()))
    """)
    assert "TELEMETRY_TP_OK" in out
