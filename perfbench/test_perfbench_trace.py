"""The trace reduction on a hand-made trace, and reading a real profiler
file (host spans) with ``jax.profiler``."""
import jax
import jax.numpy as jnp
import pytest

from perfbench import trace

DEV = "/device:TPU:0"


def _raw():
    host = [("bench.window", 10.0, 1.0),
            ("bench.step", 10.0, 0.6), ("bench.submit", 10.6, 0.1),
            ("bench.wait", 10.7, 0.3)]
    ops = [("fusion.1", 10.05, 0.10), ("_dequant_kernel", 10.15, 0.20),
           ("_dequant_kernel", 10.30, 0.10),        # overlaps the previous
           ("copy.2", 10.50, 0.05), ("late", 10.95, 0.20),  # clipped at 11.0
           ("before", 9.0, 0.5)]                     # outside the window
    mods = [("jit_decode_chunk_fn(1)", 10.05, 0.45),
            ("jit_prefill_slot(2)", 10.5, 0.05)]
    return {"ops": {DEV: ops}, "modules": {DEV: mods}, "host": host}


def test_busy_idle_and_attribution():
    r = trace.reduce(_raw())
    assert r["window_s"] == pytest.approx(1.0)
    # busy: [10.05, 10.40] + [10.50, 10.55] + [10.95, 11.0]
    assert r["busy_s"] == pytest.approx(0.35 + 0.05 + 0.05)
    idle = r["idle_by_span"]
    # gaps: 10.00-10.05 and 10.40-10.50 in step; 10.55-10.60 in step;
    # 10.60-10.70 in submit; 10.70-10.95 in wait
    assert idle["bench.step"] == pytest.approx(0.05 + 0.10 + 0.05)
    assert idle["bench.submit"] == pytest.approx(0.10)
    assert idle["bench.wait"] == pytest.approx(0.25)
    assert r["op_count"]["_dequant_kernel"] == 2
    assert r["op_time"]["_dequant_kernel"] == pytest.approx(0.30)
    assert "before" not in r["op_time"]
    assert trace.matching(r["module_time"], ["decode_chunk_fn"]) == \
        pytest.approx(0.45)
    assert r["device_ops"][0] == ["_dequant_kernel", pytest.approx(0.30)]
    assert r["idle_gaps"][0] == ["bench.wait", pytest.approx(0.25)]


def test_short_name_of_an_hlo_instruction():
    assert trace.short_name("%grouped_dequant_matmul.75 = bf16[16,12800]"
                            "{1,0} custom-call(s8[16,4096] %fusion.283)") \
        == "grouped_dequant_matmul"
    assert trace.short_name("%while.48 = (s32[]) while(%tuple)") == "while"
    assert trace.short_name("copy") == "copy"


def test_union_merges_overlaps():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                                (3, 4)]


def test_reduce_needs_the_window_span():
    raw = _raw()
    raw["host"] = raw["host"][1:]
    with pytest.raises(ValueError):
        trace.reduce(raw)


def test_load_reads_host_spans_of_a_real_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    raw = trace.load(str(tmp_path))
    names = [h[0] for h in raw["host"]]
    assert trace.WINDOW_SPAN in names and "bench.step" in names
    win = [h for h in raw["host"] if h[0] == trace.WINDOW_SPAN][0]
    step = [h for h in raw["host"] if h[0] == "bench.step"][0]
    assert win[1] <= step[1] and step[1] + step[2] <= win[1] + win[2] + 1e-6
