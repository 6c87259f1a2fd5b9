"""A hybrid configuration at smoke size through a whole run but the chip
check and the reference, on the CPU."""
import time

import pytest

from perfbench import harness, tiny


@pytest.fixture(autouse=True)
def _tiny_model(monkeypatch):
    monkeypatch.setattr(harness, "arch_config", tiny.arch_config)


def test_a_hybrid_cell_runs_with_no_reference():
    """jamba's smoke preset with experts in every layer goes through the
    build, warm-up, open loop and layer record of a run; with no reference
    nothing is compared and the run is not correct."""
    from perfbench import costs
    cell = tiny.cell(tiny.HYBRID)
    r = harness.Run(cell, 2**33 + 5, 2.0, False, time.perf_counter(),
                    harness.CompileClock())
    r.build()
    r.warm()
    r.serve()
    r.trace_summary = {"module_time": {}, "op_time": {}, "op_count": {},
                       "window_s": 1.0, "busy_s": 0.5}
    rec = r.layer_record(costs.peaks("TPU v5 lite"))
    work = rec["work"]
    assert rec["decode_steps"] > 0 and rec["prefill_tokens"] > 0
    assert any(work.calls[k + ".expert"] for k in ("grouped", "bitserial"))
    assert rec["ops"]["decode"] > 0 and rec["ops"]["prefill"] > 0
    assert sum(len(s.tokens) for s in r.served.values()) > 0
    r.free()
    assert r.check() == {}
