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
