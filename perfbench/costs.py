"""Operations and bytes of the work a serving window did, from shapes the
benchmark knows, and the peaks they are held against.

A projection ``y = x @ W`` with ``x`` [M, K] and ``W`` [K, N] served at
``w_bits`` is ``2*M*K*N`` operations and, at the least, ``K*N*w_bits/8``
bytes of weights plus int8 activations (``M*K``), bfloat16 outputs
(``2*M*N``) and f32 scales (``4*(M+N)``).  That is the algorithm's own work,
whatever layout the store keeps: a call's least time is
``max(ops / int8 peak, bytes / HBM bandwidth)``.  Where one call serves rows
of several tiers, the widest tier's ``w_bits`` counts.

Each layer counts by its kind (``weights.layer_kinds``, from the file's
layer list and published keys):

* attention: q/k/v/o, and ``4 * heads * head_dim`` operations per key a
  token attends to;
* Mamba-2: ``in_proj`` (K = d, N = ``2*d_inner + 2*n_groups*d_state +
  n_heads``) and ``out_proj`` (K = ``d_inner``, N = d); the recurrence per
  token is ``d_inner * d_state`` multiply-adds for the state update ``S' =
  exp(dt*A) S + B (dt*x)`` and as many for the read-out ``y = C S'``, at 2
  operations per multiply-add (``4 * d_inner * d_state``; the conv, gate
  and norms are elementwise and left out);
* a SwiGLU MLP: gate, up, down;
* experts: the router (``d x`` the published count of experts; model
  operations only: it is float32, not a quantized call), the routed experts
  at ``k * held / published`` experts' SwiGLU per token in expectation (the
  tokens whose experts live here), and a shared expert at its own width.

A routed-expert call is one kernel call over every expert held here (the
program maps its projection over the expert axis), so it reads every held
expert's weights; its rows are the routed rows in expectation.  Its calls
count under a key of their own, ``<kernel>.expert``: the trace names them
as the kernel they run on, and a kernel's roofline reader counts its own
key and its ``.expert`` key against the kernel's events.
"""
from __future__ import annotations

import collections
import json
import math
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from perfbench import weights

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}")
    return table[device_kind]


def projections(cfg: Dict[str, Any], layer: int
                ) -> List[Tuple[str, int, int]]:
    """(name, K, N) of layer ``layer``'s quantized 2-D projections, in the
    program's order, from the shapes the file states
    (``weights.layer_shapes``): a shared expert's included; the router (not
    quantized) and the routed experts (``expert_projections``) apart."""
    return [(name, *shape) for name, shape in
            weights.layer_shapes(cfg, layer).items()
            if name.endswith("/w") and len(shape) == 2
            and not name.endswith("router/w")]


def expert_projections(cfg: Dict[str, Any], layer: int
                       ) -> List[Tuple[str, int, int]]:
    """(name, K, N) of one routed expert's projections in layer ``layer``
    (none outside an expert layer)."""
    return [(name, *shape[1:]) for name, shape in
            weights.layer_shapes(cfg, layer).items() if len(shape) == 3]


def routed(cfg: Dict[str, Any]) -> float:
    """Routed experts' rows per token here, in expectation: ``k`` of the
    published count, of which the file holds ``num_local_experts``."""
    run = weights.served(cfg)
    return run["num_experts_per_tok"] * run["num_local_experts"] / \
        weights.published(cfg, "num_local_experts")


def kinds(cfg: Dict[str, Any]) -> List[Tuple[int, int]]:
    """(first layer, count) of each layer kind of the file's layer list, in
    order of first appearance."""
    first: Dict[Tuple[str, Optional[str]], List[int]] = {}
    for i, kind in enumerate(weights.layer_kinds(cfg)):
        first.setdefault(kind, [i, 0])[1] += 1
    return [(i, n) for i, n in first.values()]


def gemm(m: int, k: int, n: int, w_bits: int) -> Tuple[float, float]:
    """(operations, least bytes) of one quantized projection call."""
    ops = 2.0 * m * k * n
    nbytes = k * n * w_bits / 8 + m * k + 2 * m * n + 4 * (m + n)
    return ops, nbytes


def expert_gemm(rows: float, k: int, n: int, w_bits: int, experts: int
                ) -> Tuple[float, float]:
    """(operations, least bytes) of one routed-expert call: ``rows`` routed
    rows in all over ``experts`` held experts, each expert's weights read."""
    ops = 2.0 * rows * k * n
    nbytes = experts * k * n * w_bits / 8 + rows * k + 2 * rows * n + \
        4 * (rows + experts * n)
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
        self.tally(kernel, *gemm(m, k, n, w_bits), times)

    def tally(self, kernel: str, ops: float, nbytes: float,
              times: int) -> None:
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
    every layer's projections and an untied head once per step over the
    whole slot batch: with rows of two or more tiers through the grouped
    kernel, else through the single-width bit-serial kernel.  A prefill
    (``tier``, ``prompt_len``) runs every projection over its prompt and an
    untied head over its last row through the bit-serial kernel.  Routed
    experts count under ``<kernel>.expert``."""
    work = KernelWork(pk)
    tiers = cfg["tiers"]
    per_kind = [(count, projections(cfg, i), expert_projections(cfg, i))
                for i, count in kinds(cfg)]
    untied = not cfg["tie_word_embeddings"]
    d, vp = cfg["hidden_size"], cfg["padded_vocab"]
    held = cfg.get("num_local_experts", 0)
    share = routed(cfg) if held else 0.0

    def layers(kernel: str, m: int, bits: int, times: int) -> None:
        for count, projs, experts in per_kind:
            for _, k, n in projs:
                work.add(kernel, m, k, n, bits, times=count * times)
            for _, k, n in experts:
                work.tally(kernel + ".expert",
                           *expert_gemm(m * share, k, n, bits, held),
                           count * times)

    for ch in decode_chunks:
        groups = ch["groups"]
        m = sum(r for _, r in groups)
        bits = max(tiers[t]["w_bits"] for t, _ in groups)
        kernel = "grouped" if len(groups) > 1 else "bitserial"
        layers(kernel, m, bits, ch["n_steps"])
        if untied:
            work.add(kernel, m, d, vp, bits, times=ch["n_steps"])
    for pf in prefills:
        bits = tiers[pf["tier"]]["w_bits"]
        layers("bitserial", pf["prompt_len"], bits, 1)
        if untied:
            work.add("bitserial", 1, d, vp, bits)
    return work


def model_ops(cfg: Dict[str, Any], contexts: Iterable[int],
              logit_rows: int) -> float:
    """Operations the model needs: every layer's projections (the router
    and the routed experts' expected share included) and Mamba-2
    recurrence for each token processed, attention of each token over its
    context (``contexts``: the keys each processed token attends to), and
    the head for each row of logits the program computes."""
    run = weights.served(cfg)
    macs, att_layers = 0.0, 0
    for i, count in kinds(cfg):
        shapes = weights.layer_shapes(cfg, i)
        macs += count * sum(k * n for _, k, n in projections(cfg, i))
        if "moe/router/w" in shapes:
            macs += count * math.prod(shapes["moe/router/w"])
            macs += count * routed(cfg) * sum(
                k * n for _, k, n in expert_projections(cfg, i))
        if "mamba/in_proj/w" in shapes:
            macs += 2 * count * run["mamba_expand"] * run["hidden_size"] * \
                run["mamba_d_state"]
        else:
            att_layers += count
    per_tok = 2.0 * macs
    att = 4.0 * att_layers * cfg["num_attention_heads"] * cfg["head_dim"] \
        if att_layers else 0.0
    n_tok, ctx = 0, 0
    for c in contexts:
        n_tok += 1
        ctx += c
    head = 2.0 * cfg["hidden_size"] * cfg["padded_vocab"] * logit_rows
    return per_tok * n_tok + att * ctx + head
