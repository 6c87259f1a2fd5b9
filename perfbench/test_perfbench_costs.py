"""Roofline and model-ops counts against hand-computed values for granite's
q projection at 8/8 and 2/2."""
import json
import os

import pytest

from perfbench import costs

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "configs", "granite-3-8b.json")) as f:
    GRANITE = json.load(f)
PK = costs.peaks("TPU v5 lite")


def test_peaks_table():
    assert PK["int8_ops"] == 393e12 and PK["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")


def test_q_proj_at_8_and_2_bits():
    m, k, n = 16, 4096, 4096
    ops, b8 = costs.gemm(m, k, n, 8)
    assert ops == 2 * 16 * 4096 * 4096 == 536870912
    # weights 16 MiB + int8 acts 64 KiB + bf16 out 128 KiB + scales
    assert b8 == 16777216 + 65536 + 131072 + 4 * (16 + 4096)
    _, b2 = costs.gemm(m, k, n, 2)
    assert b2 == 4194304 + 65536 + 131072 + 16448
    w = costs.KernelWork(PK)
    w.add("grouped", m, k, n, 8)
    assert w.least_s["grouped"] == pytest.approx(b8 / 819e9)   # bandwidth-bound
    w.add("bitserial", 2048, k, n, 8)
    ops_p, b_p = costs.gemm(2048, k, n, 8)
    assert w.least_s["bitserial"] == pytest.approx(
        max(ops_p / 393e12, b_p / 819e9))
    assert ops_p / 393e12 > b_p / 819e9                          # compute-bound


def test_kernel_work_counts_calls():
    chunks = [{"groups": (("8/8", 5), ("2/2", 11)), "n_steps": 8},
              {"groups": (("2/2", 16),), "n_steps": 8}]
    prefills = [{"tier": "2/2", "prompt_len": 300}]
    w = costs.kernel_work(GRANITE, PK, chunks, prefills)
    layers = GRANITE["num_hidden_layers"]
    assert w.calls["grouped"] == 8 * 7 * layers        # tied head: no kernel
    assert w.calls["bitserial"] == 8 * 7 * layers + 7 * layers
    ops8, b8 = costs.gemm(16, 4096, 4096, 8)
    ops2, b2 = costs.gemm(16, 4096, 4096, 2)
    per_q = [ops8, ops2]
    assert w.ops["grouped"] > 0 and per_q[0] == per_q[1]
    # the mixed chunk reads weights at the widest tier (8 bits)
    q_only = costs.KernelWork(PK)
    q_only.add("x", 16, 4096, 4096, 8, times=8 * layers)
    assert w.bytes["grouped"] > q_only.bytes["x"]


def test_model_ops():
    contexts = [100, 101]
    ops = costs.model_ops(GRANITE, contexts, logit_rows=2)
    layers, d, ff = 16, 4096, 12800
    proj = 2 * (d * 4096 + 2 * d * 1024 + 4096 * d + 3 * d * ff)
    att = 4 * 32 * 128 * (100 + 101)
    head = 2 * d * 49408 * 2
    assert ops == pytest.approx(layers * (2 * proj + att) + head)


def _granite4(held=72, layers=40):
    """granite-4.0-h-small's published keys (the program's departures left
    out), ``held`` of its 72 experts and ``layers`` of its 40 layers."""
    from perfbench import tiny
    cfg = tiny.granite4_h_small()
    for key in ("departures", "program"):
        del cfg[key]
    cfg.update(num_hidden_layers=layers, num_local_experts=held)
    cfg["reduced"] = {"num_local_experts": f"72 -> {held}: held here"} \
        if held < 72 else {}
    return cfg


def test_hybrid_model_ops_by_layer_kind():
    d = 4096
    in_proj = d * 16768                      # 2*8192 + 2*1*128 + 128
    mamba = in_proj + 8192 * d + 2 * 8192 * 128   # + out_proj, recurrence
    attn = d * 4096 + 2 * d * 1024 + 4096 * d
    routed = 10 * 3 * d * 768                # top-10 at full share
    shared = 3 * d * 1536
    router = d * 72
    per_layer = 36 * mamba + 4 * attn + 40 * (router + routed + shared)
    ops = costs.model_ops(_granite4(), [100, 101], logit_rows=2)
    att = 4 * 4 * 32 * 128 * (100 + 101)
    head = 2 * d * 100352 * 2
    assert ops == 2 * 2 * per_layer + att + head
    assert costs.projections(_granite4(), 0)[0] == \
        ("mamba/in_proj/w", 4096, 16768)
    assert costs.kinds(_granite4()) == [(0, 36), (5, 4)]


def test_held_experts_count_their_share():
    full, held = _granite4(layers=10), _granite4(held=18, layers=10)
    assert costs.routed(full) == 10 and costs.routed(held) == 2.5
    one = costs.model_ops(full, [1], 0) - costs.model_ops(held, [1], 0)
    assert one == 2 * 10 * (10 - 2.5) * 3 * 4096 * 768   # the router stays


def test_hybrid_kernel_work_by_kind():
    cfg = _granite4(held=18, layers=10)
    chunks = [{"groups": (("8/8", 5), ("2/2", 11)), "n_steps": 8}]
    prefills = [{"tier": "8/8", "prompt_len": 512}]
    w = costs.kernel_work(cfg, PK, chunks, prefills)
    # per step: 9 Mamba layers x (in, out, shared x3) + attention x (q, k,
    # v, o, shared x3); tied head; routed experts 3 calls a layer
    assert w.calls["grouped"] == 8 * (9 * 5 + 7)
    assert w.calls["grouped.expert"] == 8 * 10 * 3
    assert w.calls["bitserial"] == 9 * 5 + 7
    assert w.calls["bitserial.expert"] == 10 * 3
    ops, nbytes = costs.expert_gemm(16 * 2.5, 4096, 768, 8, 18)
    assert ops == 2 * 40 * 4096 * 768
    assert nbytes == 18 * 4096 * 768 + 40 * 4096 + 2 * 40 * 768 + \
        4 * (40 + 18 * 768)


def test_roofline_reads_a_kernel_with_its_expert_calls():
    from perfbench.metrics import _roofline
    w = costs.KernelWork(PK)
    w.add("bitserial", 16, 4096, 4096, 8, times=2)
    w.tally("bitserial.expert", *costs.expert_gemm(40, 4096, 768, 8, 18), 3)
    rec = {"work": w, "kernels": {"bitserial": ["packed_bitserial_matmul"]},
           "trace": {"op_time": {"packed_bitserial_matmul": 1e-3},
                     "op_count": {"packed_bitserial_matmul": 5}}}
    want = 100 * (w.least_s["bitserial"] + w.least_s["bitserial.expert"]) \
        / 1e-3
    assert _roofline.share(rec, "bitserial") == pytest.approx(want)
    rec["trace"]["op_count"]["packed_bitserial_matmul"] = 6
    assert _roofline.share(rec, "bitserial") is None


def test_granite_layer_record_is_unchanged():
    """``layer_record``'s work and model operations for the granite cell on
    a fixed step list, to the bit, as before layers were counted by kind."""
    import types
    from perfbench import harness
    cell = harness.load_cell("granite-3-8b.mixed-decode-over")
    run = harness.Run(cell, 7, 40.0, True, 0.0, types.SimpleNamespace(
        lowered_between=lambda a, b: 0))
    S = harness.StepRecord
    run.steps = [
        S(0.5, 0.9, 0, None, [("8/8", 130)], []),
        S(1.0, 1.5, 8, (("8/8", 5), ("2/2", 11)), [],
          [700 + i for i in range(8 * 16)]),
        S(1.6, 2.0, 3, (("2/2", 16),), [("2/2", 2019)],
          [900 + i for i in range(3 * 16)]),
        S(2.1, 2.5, 8, (("8/8", 16),), [], [300 + i for i in range(8 * 16)]),
        S(9.0, 9.5, 8, (("8/8", 16),), [], [1] * 128)]
    run.window = (0.4, 3.0)
    run.trace_summary, run.compile_s, run.stats_delta = {}, 1.0, {}
    rec = run.layer_record(PK)
    w = rec["work"]
    assert {k: [w.calls[k], w.ops[k].hex(), w.bytes[k].hex(),
                w.least_s[k].hex()] for k in w.calls} == {
        "bitserial": [1456, "0x1.af65800000000p+43", "0x1.0e40809800000p+35",
                      "0x1.249df585e2ac2p-4"],
        "grouped": [896, "0x1.7c00000000000p+39", "0x1.7fe2380000000p+34",
                    "0x1.01aed1261ef2fp-5"]}
    assert {k: v.hex() for k, v in rec["ops"].items()} == {
        "decode": "0x1.eb68a00000000p+40", "prefill": "0x1.9e62f48000000p+43"}
    assert (rec["decode_steps"], rec["decode_tokens"],
            rec["prefill_tokens"]) == (19, 304, 2149)
