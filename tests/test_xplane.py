"""repro.telemetry.xplane: the reduction of a profiler trace on hand-made
lists (device time by program and layer scope, span self time with nested
children, idle time by the innermost engine phase), and a real
``jax.profiler`` trace around a few ``ServeEngine.step`` calls."""
import jax
import numpy as np
import pytest

import repro.telemetry as telemetry_mod
from repro.configs import reduced_config
from repro.core.policy import uniform_schedule
from repro.models.layers import Runtime
from repro.models.transformer import LM
from repro.serve import Request, ServeEngine
from repro.telemetry import xplane
from repro.telemetry.xplane import Op, Span

DEV = "/device:TPU:0"
DEC = "jit_decode_chunk_fn"


def _trace():
    spans = [Span("serve.step", 10.0, 1.0),
             Span("serve.admit", 10.0, 0.3, args=(("uid", 7),)),
             Span("serve.prefill", 10.05, 0.05),
             Span("serve.wait_first_token", 10.1, 0.15),
             Span("serve.decode", 10.3, 0.1),
             Span("serve.wait_decode", 10.4, 0.5),
             Span("serve.emit", 10.9, 0.05),
             Span("serve.submit", 11.2, 0.1),        # outside any step
             Span("serve.step", 12.5, 1.0)]          # past the window
    ops = [Op(DEV, "jit_prefill_slot", "linear", 10.1, 0.1, "fusion.1"),
           Op(DEV, "jit_prefill_slot", "other", 10.2, 0.02, "copy.1"),
           Op(DEV, DEC, "while", 10.35, 0.6, "while.3"),   # container
           Op(DEV, DEC, "linear", 10.35, 0.3, "grouped_dequant_matmul.2"),
           Op(DEV, DEC, "attention", 10.65, 0.1, "fusion.7"),
           Op(DEV, DEC, "other", 10.75, 0.15, "copy.4"),
           Op(DEV, DEC, "lm_head", 10.9, 0.2, "fusion.9")]  # clipped
    mods = [Op(DEV, "jit_prefill_slot", "", 10.1, 0.12),
            Op(DEV, DEC, "", 10.35, 0.6)]
    return {"ops": ops, "modules": mods, "spans": spans}


def test_scope_and_module_time():
    r = xplane.reduce(_trace(), window=(10.0, 11.0))
    assert r["window_s"] == pytest.approx(1.0)
    # busy: [10.10, 10.22] + [10.35, 11.0]
    assert r["busy_s"] == pytest.approx(0.12 + 0.65)
    st = r["scope_time"]
    assert st[f"{DEC}/linear"] == pytest.approx(0.3)
    assert st[f"{DEC}/attention"] == pytest.approx(0.1)
    assert st[f"{DEC}/other"] == pytest.approx(0.15)
    assert st[f"{DEC}/lm_head"] == pytest.approx(0.1)       # clipped at 11
    assert st["jit_prefill_slot/linear"] == pytest.approx(0.1)
    assert not any(k.endswith("/while") for k in st)        # container
    assert r["module_time"][DEC] == pytest.approx(0.6)
    assert r["module_time"]["jit_prefill_slot"] == pytest.approx(0.12)


def test_span_self_time_and_host_time_per_round():
    r = xplane.reduce(_trace(), window=(10.0, 11.0))
    sp = r["span_time"]
    assert sp["serve.step"]["count"] == 1                   # one in window
    assert sp["serve.step"]["total_s"] == pytest.approx(1.0)
    # step less its children: admit 0.3, decode 0.1, wait 0.5, emit 0.05
    assert sp["serve.step"]["self_s"] == pytest.approx(0.05)
    # admit less prefill 0.05 and wait_first_token 0.15
    assert sp["serve.admit"]["self_s"] == pytest.approx(0.1)
    assert sp["serve.wait_decode"]["self_s"] == pytest.approx(0.5)
    assert "serve.submit" not in sp                         # not wholly in
    # the round's 1.0 s less its two waits (0.15 + 0.5)
    assert r["round_host_s"] == pytest.approx(0.35)


def test_idle_by_innermost_span():
    r = xplane.reduce(_trace(), window=(10.0, 11.0))
    idle = r["idle_by_span"]
    # gaps: 10.00-10.10 (admit 10.00-10.05, prefill 10.05-10.10),
    # 10.22-10.35 (wait_first_token to 10.25, admit to 10.30, decode)
    assert idle["serve.admit"] == pytest.approx(0.05 + 0.05)
    assert idle["serve.prefill"] == pytest.approx(0.05)
    assert idle["serve.wait_first_token"] == pytest.approx(0.03)
    assert idle["serve.decode"] == pytest.approx(0.05)
    assert sum(idle.values()) == pytest.approx(1.0 - r["busy_s"])


def test_idle_outside_every_span_is_none():
    t = {"ops": [Op(DEV, DEC, "linear", 1.0, 1.0, "dot.1")], "modules": [],
         "spans": [Span("serve.step", 2.5, 0.5)]}
    r = xplane.reduce(t, window=(0.0, 4.0))
    assert r["idle_by_span"] == pytest.approx(
        {xplane.NO_SPAN: 2.5, "serve.step": 0.5})
    assert r["round_host_s"] == pytest.approx(0.5)


def test_program_id_of_a_program_run():
    assert xplane._program_id("jit_decode_chunk_fn(42)") == 42
    assert xplane._program_id("jit_f") == -1


# ------------------------------------------------------ a real trace
@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("granite-3-8b")
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = uniform_schedule({"8/8": (8, 8), "2/2": (2, 2)},
                             kv_tiers={"8/8": None, "2/2": 4})
    rt = Runtime(policy=sched.policy_for(), mode="serve", schedule=sched)
    return cfg, model, params, rt


def _requests(cfg):
    rng = np.random.default_rng(3)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               size=4 + i),
                    max_new_tokens=7, tier=("8/8", "2/2")[i % 2])
            for i in range(4)]


def test_profiler_trace_around_engine_steps(setup, tmp_path, monkeypatch):
    """Engine phases nest inside ``serve.step`` on the profiler's clock,
    device ops carry the layer scopes, and tracing changes no token, adds
    no host fence and takes no telemetry hook."""
    cfg, model, params, rt = setup
    ref = ServeEngine(model, params, rt, max_batch=2, max_len=32,
                      decode_chunk=4)
    want = ref.run(_requests(cfg))

    eng = ServeEngine(model, ref.params, rt, max_batch=2, max_len=32,
                      decode_chunk=4)
    for r in _requests(cfg):
        eng.submit(r)
    eng.step()                                   # compiles outside
    hooks = telemetry_mod.HOOK_CALLS
    steps0 = eng.stats.decode_steps

    def forbidden(*a, **k):
        raise AssertionError("a span fenced the device")

    jax.profiler.start_trace(str(tmp_path))
    try:
        with monkeypatch.context() as m:
            m.setattr(jax, "block_until_ready", forbidden)
            for _ in range(3):
                eng.step()
    finally:
        jax.profiler.stop_trace()
    assert telemetry_mod.HOOK_CALLS == hooks
    traced_steps = eng.stats.decode_steps - steps0
    assert eng.drain() == want

    t = xplane.load(str(tmp_path))
    steps = [s for s in t["spans"] if s.name == "serve.step"]
    assert len(steps) == 3
    inner = [s for s in t["spans"] if s.name != "serve.step"]
    names = {s.name for s in inner}
    assert {"serve.decode", "serve.wait_decode", "serve.emit"} <= names
    for s in inner:
        assert any(p.start <= s.start and s.end <= p.end + 1e-6
                   for p in steps), s
    decode = [dict(s.args) for s in inner if s.name == "serve.decode"]
    assert sum(a["n_steps"] for a in decode) == traced_steps
    assert all(a["layout"] in ("8/8x1+2/2x1", "8/8x2", "2/2x2")
               for a in decode), decode
    scopes = {o.scope for o in t["ops"] if o.module == "jit_decode_chunk_fn"}
    assert {"linear", "attention", "lm_head", "kv_write"} <= scopes
    r = xplane.reduce(t)
    assert r["span_time"]["serve.step"]["count"] == 3
    assert 0.0 < r["round_host_s"] < r["span_time"]["serve.step"]["total_s"]
