"""Operations and bytes of the work a serving window did, from shapes the
benchmark knows, and the peaks they are held against.

A projection ``y = x @ W`` with ``x`` [M, K] and ``W`` [K, N] served at
``w_bits`` is ``2*M*K*N`` operations and, at the least, ``K*N*w_bits/8``
bytes of weights plus int8 activations (``M*K``), bfloat16 outputs
(``2*M*N``) and f32 scales (``4*(M+N)``).  That is the algorithm's own work,
whatever layout the store keeps: a call's least time is
``max(ops / int8 peak, bytes / HBM bandwidth)``.  Where one call serves rows
of several tiers, the widest tier's ``w_bits`` counts.
"""
from __future__ import annotations

import collections
import json
import os
from typing import Any, Dict, Iterable, List, Sequence, Tuple

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}")
    return table[device_kind]


def projections(cfg: Dict[str, Any]) -> List[Tuple[str, int, int]]:
    """(name, K, N) of the quantized projections of one layer."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h, kvh, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return [("q_proj", d, h * dh), ("k_proj", d, kvh * dh),
            ("v_proj", d, kvh * dh), ("o_proj", h * dh, d),
            ("gate_proj", d, ff), ("up_proj", d, ff), ("down_proj", ff, d)]


def gemm(m: int, k: int, n: int, w_bits: int) -> Tuple[float, float]:
    """(operations, least bytes) of one quantized projection call."""
    ops = 2.0 * m * k * n
    nbytes = k * n * w_bits / 8 + m * k + 2 * m * n + 4 * (m + n)
    return ops, nbytes


class KernelWork:
    """Calls, operations, bytes and least seconds per kernel."""

    def __init__(self, pk: Dict[str, float]) -> None:
        self.pk = pk
        self.calls: Dict[str, int] = collections.defaultdict(int)
        self.ops: Dict[str, float] = collections.defaultdict(float)
        self.bytes: Dict[str, float] = collections.defaultdict(float)
        self.least_s: Dict[str, float] = collections.defaultdict(float)

    def add(self, kernel: str, m: int, k: int, n: int, w_bits: int,
            times: int = 1) -> None:
        ops, nbytes = gemm(m, k, n, w_bits)
        self.calls[kernel] += times
        self.ops[kernel] += ops * times
        self.bytes[kernel] += nbytes * times
        self.least_s[kernel] += times * max(
            ops / self.pk["int8_ops"], nbytes / self.pk["hbm_bytes_per_s"])


def kernel_work(cfg: Dict[str, Any], pk: Dict[str, float],
                decode_chunks: Sequence[Dict[str, Any]],
                prefills: Sequence[Dict[str, Any]]) -> KernelWork:
    """The quantized GEMM calls of the programs a window dispatched.

    A decode chunk (``groups``: ``[[tier, rows], ...]``, ``n_steps``) runs
    every projection and an untied head once per step over the whole slot
    batch: with rows of two or more tiers through the grouped kernel, else
    through the single-width bit-serial kernel.  A prefill (``tier``,
    ``prompt_len``) runs every projection over its prompt and an untied head
    over its last row through the bit-serial kernel."""
    work = KernelWork(pk)
    tiers = cfg["tiers"]
    layers = cfg["num_hidden_layers"]
    projs = projections(cfg)
    untied = not cfg["tie_word_embeddings"]
    d, vp = cfg["hidden_size"], cfg["padded_vocab"]
    for ch in decode_chunks:
        groups = ch["groups"]
        m = sum(r for _, r in groups)
        bits = max(tiers[t]["w_bits"] for t, _ in groups)
        kernel = "grouped" if len(groups) > 1 else "bitserial"
        for _, k, n in projs:
            work.add(kernel, m, k, n, bits, times=layers * ch["n_steps"])
        if untied:
            work.add(kernel, m, d, vp, bits, times=ch["n_steps"])
    for pf in prefills:
        bits = tiers[pf["tier"]]["w_bits"]
        for _, k, n in projs:
            work.add("bitserial", pf["prompt_len"], k, n, bits, times=layers)
        if untied:
            work.add("bitserial", 1, d, vp, bits)
    return work


def model_ops(cfg: Dict[str, Any], contexts: Iterable[int],
              logit_rows: int) -> float:
    """Operations the model needs: every projection for each token
    processed, attention of each token over its context (``contexts``: the
    keys each processed token attends to), and the head for each row of
    logits the program computes."""
    layers = cfg["num_hidden_layers"]
    per_tok = 2.0 * layers * sum(k * n for _, k, n in projections(cfg))
    att = 4.0 * layers * cfg["num_attention_heads"] * cfg["head_dim"]
    n_tok, ctx = 0, 0
    for c in contexts:
        n_tok += 1
        ctx += c
    head = 2.0 * cfg["hidden_size"] * cfg["padded_vocab"] * logit_rows
    return per_tok * n_tok + att * ctx + head
