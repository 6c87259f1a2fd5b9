"""Tensor-parallel serving collectives: the quantized wire INSIDE the
jitted decode scan, bit-identical to the unsharded engine.

``tp_matmul`` proved the wire format on a standalone MLP block; this module
plugs the same idea into ``ServeEngine``'s hot path so ONE engine spans a
mesh.  Layout (deliberately not classic Megatron column/row pairs):

* EVERY sharded projection is N-sharded on its LAST weight axis — q/k/v
  over heads, gate/up over d_ff, AND o_proj/down_proj over d_model.  An
  N-shard never splits a K-reduction, so each device's integer GEMM is an
  exact column slice of the unsharded accumulator; classic row-parallel
  o/down would psum CONTINUOUS partials, whose float summation order is
  device-count-dependent and breaks token identity.
* q/k/v/gate/up read the REPLICATED residual: activation quantization sees
  the full row on every device, so codes and scales are bitwise equal to
  the unsharded engine's with no collective at all.
* o_proj/down_proj read FEATURE-SHARDED inputs (local attention heads /
  local d_ff).  The exactness chain: local ``amax`` -> ``lax.pmax`` (max is
  exact) -> the mesh-shared scale equals the unsharded per-row scale ->
  local codes are an exact K-slice of the unsharded codes -> all-gather the
  CODES (int8, or bit-packed at 4/2-bit tiers — THE quantized wire) ->
  full-K integer GEMM against the local N-shard -> elementwise dequant ->
  all-gather bf16 outputs back to the replicated residual.  Every step is
  either exact integer math or the very same f32 ops the unsharded graph
  runs, so tokens match bit for bit.
* Scales never ride the wire: the pmax already left the per-row f32 scale
  replicated (an improvement over ``tp_matmul``'s bf16-scale gather).

Plane-prefix truncation commutes with this sharding because superplane
codes are per-COLUMN: truncating then slicing columns equals slicing then
truncating, so all tier machinery (mixed row groups, ``fused_decode``,
mid-stream migration) works unchanged on shards — see
``tests/test_sharded_serving.py`` and docs/distributed.md.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

# Projections that read feature-sharded inputs and therefore need the
# quantized gather.  Matched on the layer-name suffix ``models/layers``
# passes to ``linear`` (``layers.pos{i}.attn.o_proj`` etc.); ``.moe.`` and
# ``.mamba.`` projections stay replicated and never match.
_GATHERED_SUFFIXES = (".attn.o_proj", ".mlp.down_proj")


@dataclasses.dataclass(frozen=True)
class TPConfig:
    """Static tensor-parallel context threaded through ``Runtime.tp``.

    Hashable (it rides jit-static Runtime fields): ``n`` devices on mesh
    axis ``axis``; ``kv_shards`` says whether k/v projections and the KV
    arena shard over KV heads (requires ``num_kv_heads % n == 0``) or stay
    replicated (the MQA ``num_kv_heads == 1`` fallback, where every local
    query head reads the one shared KV head)."""

    n: int
    axis: str = "model"
    kv_shards: bool = True

    def gathers(self, name: str) -> bool:
        """True for projections whose input is feature-sharded (o/down)."""
        return name.endswith(_GATHERED_SUFFIXES)


# ------------------------------------------------------- mesh-shared ranges
def _act_quant_pmax(x: jax.Array, bits: int,
                    axis_name: str) -> Tuple[jax.Array, jax.Array]:
    """``ref.act_quant_ref`` (signed) with the row max shared by ``pmax``.

    ``x`` holds each row's K-shard; the max over the full row is the max of
    the shard maxima (exact), so scale and codes are bitwise equal to the
    unsharded oracle's — each device ends up with the K-slice of the exact
    unsharded codes plus the replicated f32 scale."""
    qmax = (1 << (bits - 1)) - 1
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    amax = jax.lax.pmax(amax, axis_name)
    scale = ref.quant_scale(amax, qmax)
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _act_quant_rows_pmax(x: jax.Array, row_groups: Any,
                         perm: Optional[jax.Array],
                         axis_name: str) -> Tuple[jax.Array, jax.Array]:
    """``ops._quantize_activations_rows``'s oracle branch with pmax ranges.

    Mirrors the unsharded helper exactly — un-permuted full-batch pass with
    a per-row f32 qmax, results gathered by ``perm`` — so mixed-tier rows
    keep the bitwise-stability contract across the mesh."""
    lead, k = x.shape[:-1], x.shape[-1]
    qmax_sorted = jnp.asarray(np.concatenate([
        np.full((rows,), float((1 << (g.a_bits - 1)) - 1), np.float32)
        for rows, g in row_groups]))
    if perm is not None:
        qmax_rows = jnp.take(qmax_sorted, jnp.argsort(perm), axis=0)
    else:
        qmax_rows = qmax_sorted
    shape = (x.shape[0],) + (1,) * (x.ndim - 1)
    qmax_full = jnp.broadcast_to(qmax_rows.reshape(shape),
                                 (*lead, 1)).reshape(-1, 1)
    x2 = x.astype(jnp.float32).reshape(-1, k)
    amax = jnp.max(jnp.abs(x2), axis=-1, keepdims=True)
    amax = jax.lax.pmax(amax, axis_name)
    scale = ref.quant_scale(amax, qmax_full)
    q = jnp.clip(jnp.round(x2 / scale), -qmax_full - 1.0,
                 qmax_full).astype(jnp.int8)
    s = scale.astype(jnp.float32)
    qr, sr = q.reshape(*lead, k), s.reshape(*lead, 1)
    if perm is not None:
        qr = jnp.take(qr, perm, axis=0)
        sr = jnp.take(sr, perm, axis=0)
    return qr, sr


# -------------------------------------------------- bit-serial wire format
def wire_pack(q: jax.Array, bits: int) -> jax.Array:
    """Pack signed ``bits``-wide int8 codes, ``8 // bits`` per byte.

    [..., K] -> uint8 [..., K * bits / 8]; code ``j`` of a block lands at
    bit offset ``bits * j`` (two's complement at width ``bits``).  Packing
    is per-K-block and in-order, so it commutes with a tiled all-gather
    along K: unpack(gather(pack(q))) == gather(q)."""
    f = 8 // bits
    mask = jnp.uint8((1 << bits) - 1)
    u = q.astype(jnp.uint8) & mask
    blk = u.reshape(*u.shape[:-1], u.shape[-1] // f, f)
    packed: jax.Array = functools.reduce(
        jnp.bitwise_or,
        [blk[..., j] << jnp.uint8(bits * j) for j in range(f)])
    return packed


def wire_unpack(p: jax.Array, bits: int) -> jax.Array:
    """Inverse of :func:`wire_pack`: uint8 [..., K*bits/8] -> int8 [..., K]
    with sign extension from width ``bits``."""
    f = 8 // bits
    mask = jnp.uint8((1 << bits) - 1)
    fields = jnp.stack([(p >> jnp.uint8(bits * j)) & mask for j in range(f)],
                       axis=-1)
    u = fields.reshape(*p.shape[:-1], p.shape[-1] * f).astype(jnp.int8)
    half = jnp.int8(1 << (bits - 1))
    return jnp.where(u >= half, u - jnp.int8(1 << bits), u)


def wire_bytes_per_element(a_bits: int, signed: bool = True) -> float:
    """Wire bytes per gathered activation element under the bit-serial
    format: 8/6-bit tiers ride raw int8 (1 byte), 4/2-bit tiers pack 2/4
    codes per byte.  The f32 baseline is 4 bytes."""
    return a_bits / 8.0 if signed and a_bits in (2, 4) else 1.0


def gather_codes(q: jax.Array, bits: int, axis_name: str, *,
                 signed: bool = True) -> jax.Array:
    """All-gather activation codes tiled along K — the quantized wire.

    4/2-bit tiers travel bit-packed (uint8, ``8 // bits`` codes per byte)
    when the local K divides the pack factor; 8/6-bit tiers and unsigned
    codes travel as raw int8.  Returns the full-K int8 codes, identical on
    every device to the unsharded quantizer's output."""
    f = 8 // bits if bits in (2, 4) else 1
    if signed and f > 1 and q.shape[-1] % f == 0:
        p = wire_pack(q, bits)
        p_all: jax.Array = jax.lax.all_gather(p, axis_name, axis=p.ndim - 1,
                                              tiled=True)
        return wire_unpack(p_all, bits)
    q_all: jax.Array = jax.lax.all_gather(q, axis_name, axis=q.ndim - 1,
                                          tiled=True)
    return q_all


# ----------------------------------------------------- gathered projections
def gathered_matmul(x: jax.Array, qw: Any, prec: Any, *, tp: TPConfig,
                    out_dtype: Any = None) -> jax.Array:
    """One o/down projection under TP, single precision (inside shard_map).

    x: [..., K/n] feature-sharded input; qw: the local weight N-shard with
    FULL K rows.  Quantize with the pmax-shared range, gather codes over
    the wire, run the local plane-prefix GEMM + dequant (the same
    ``ops.dequant_matmul`` graph as unsharded), and gather the bf16 output
    columns back to the replicated [..., N_full]."""
    if not prec.a_signed:
        raise ValueError("TP gathered projections need signed activations "
                         "(the pmax-shared range is symmetric)")
    out_dtype = x.dtype if out_dtype is None else out_dtype
    q, s = _act_quant_pmax(x.astype(jnp.float32), prec.a_bits, tp.axis)
    q_all = gather_codes(q, prec.a_bits, tp.axis, signed=prec.a_signed)
    y_loc = ops.dequant_matmul(q_all, s, qw, prec, out_dtype)
    return _output_gather(y_loc, tp)


def gathered_grouped_matmul(x: jax.Array, qw: Any, row_groups: Any,
                            perm: Optional[jax.Array], *,
                            tp: TPConfig) -> jax.Array:
    """Mixed-tier o/down projection under TP (inside shard_map).

    The sharded twin of ``ops.fused_decode_linear``: ONE pmax-ranged
    activation quantization over the full un-permuted batch, per-GROUP
    quantized gathers (each group's rows travel at ITS ``a_bits`` — the
    bit-serial wire), then the unchanged group-switching GEMM + dequant
    epilogue via ``pre_quant``, and the bf16 output gather.  Returns
    PERMUTED (group-sorted) rows like the unsharded path."""
    if not all(g.a_signed for _, g in row_groups):
        raise ValueError("TP mixed-tier decode needs signed activations")
    configs = tuple(dict.fromkeys(g.a_bits for _, g in row_groups))
    if len(configs) == 1:
        q, s = _act_quant_pmax(x.astype(jnp.float32), configs[0], tp.axis)
        if perm is not None:
            q = jnp.take(q, perm, axis=0)
            s = jnp.take(s, perm, axis=0)
    else:
        q, s = _act_quant_rows_pmax(x, row_groups, perm, tp.axis)
    gathered = []
    off = 0
    for rows, g in row_groups:
        gathered.append(gather_codes(q[off:off + rows], g.a_bits, tp.axis))
        off += rows
    q_all = jnp.concatenate(gathered, axis=0)
    y_loc = ops.fused_decode_linear(x, qw, row_groups, perm,
                                    pre_quant=(q_all, s),
                                    out_dtype=x.dtype)
    return _output_gather(y_loc, tp)


def _output_gather(y_loc: jax.Array, tp: TPConfig) -> jax.Array:
    """Gather the output columns back to the replicated [..., N_full] in
    the residual dtype — the only float collective of the wire.  The
    ``tp_output_gather`` scope names it in the compiled HLO's op_name, so
    an audit can tell it from the quantized code gathers."""
    with jax.named_scope("tp_output_gather"):
        y: jax.Array = jax.lax.all_gather(y_loc, tp.axis,
                                          axis=y_loc.ndim - 1, tiled=True)
    return y


# --------------------------------------------------------------- accounting
def decode_wire_stats(cfg: Any, tp: TPConfig,
                      groups: Any) -> Dict[str, float]:
    """Analytic wire bytes for ONE decode step of the whole stack.

    ``groups``: the static ``(rows, a_bits)`` pairs of the decode batch (a
    free-slot row rides its group like the real layout).  Per period the
    quantized wire carries the o_proj gather (H*Dh elements per row) and
    the down_proj gather (d_ff elements per row) at each row's wire width;
    each of the ``n`` devices transmits its 1/n shard to the other n-1
    peers (ring all-gather).  The bf16 output gathers and the 4-byte pmax
    scalars are reported separately; the f32 baseline prices the SAME
    gathered elements at 4 bytes."""
    n = tp.n
    pattern = cfg.period_pattern() * cfg.n_periods
    attn_layers = sum(1 for mixer, _ in pattern if mixer == "attn")
    mlp_layers = sum(1 for _, ff in pattern if ff == "mlp")
    per_row = attn_layers * cfg.num_heads * (cfg.head_dim or 0) \
        + mlp_layers * cfg.d_ff
    gathers = attn_layers + mlp_layers
    quant = 0.0
    base_f32 = 0.0
    elems = 0.0                       # elements actually transmitted
    for rows, a_bits in groups:
        bpe = wire_bytes_per_element(a_bits)
        quant += rows * per_row * bpe * (n - 1) / n
        base_f32 += rows * per_row * 4.0 * (n - 1) / n
        elems += rows * per_row * (n - 1) / n
    rows_total = sum(r for r, _ in groups)
    out_bf16 = rows_total * cfg.d_model * 2.0 * gathers * (n - 1) / n
    pmax = rows_total * 4.0 * gathers * (n - 1) / n
    return {
        "quant_gather_bytes": quant,
        "f32_gather_bytes": base_f32,
        "out_gather_bytes": out_bf16,
        "pmax_bytes": pmax,
        "gathered_elements": elems,
        "bytes_per_element": quant / elems if elems else 0.0,
        "vs_f32": base_f32 / quant if quant else float("inf"),
    }
