"""Public jit'd wrappers around the Pallas kernels + backend dispatch.

This is the surface the model layers call.  A single ``matmul`` entry point
routes through one of four backends (see core.policy.BACKENDS):

  dense       bf16/f32 matmul (fp baseline)
  fake_quant  QAT fake-quantized operands, dense matmul (training path)
  decomposed  integer plane-decomposed matmul in plain HLO (serving, dry-run)
  pallas      the Pallas TPU kernels (interpreted on CPU)

Weights for the integer paths are prepared once into a ``QuantizedWeight``
(planes + per-channel scale) — the analogue of preloading decomposed weights
into the array.

Mixed-tier decode batches (``matmul(row_groups=, perm=)``) run FUSED by
default: one per-row-range activation quantization + ONE group-switching
plane-prefix GEMM with the dequant epilogue in its flush step
(``fused_decode_linear``), instead of one dispatch chain per tier group.
``fused=False`` keeps the per-group reference path, which the fused path is
bit-identical to (tests/test_grouped_kernel.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import decompose, quant
from repro.core.policy import LayerPrecision
from repro.kernels import act_quant as act_quant_kernel
from repro.kernels import bitserial_matmul as bsm
from repro.kernels import grouped_matmul as gmm
from repro.kernels import ref

# (rows, LayerPrecision) per contiguous tier group — static, keys the trace.
RowGroups = Tuple[Tuple[int, Any], ...]
# Shared activation-quant cache: one entry per distinct quant config of ONE
# input tensor (see quantize_activations_grouped).
ActQuants = Dict[Any, Tuple[jax.Array, jax.Array]]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Pallas mode for the default backend: compiled on TPU, interpreted on
    CPU (tests, tiny runs).  Any other backend raises — the kernels never
    fall back to interpret mode where a device is attached."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run compiled on TPU or interpreted "
                       f"on CPU; the {backend!r} backend has neither")


@dataclasses.dataclass
class QuantizedWeight:
    """Decomposed, scaled integer weight — the preloaded array contents.

    Either unpacked planes (int8 [P, K, N]; paper-faithful "one column per
    plane") or the packed layout (uint8 [K, N], all 2-bit planes of one
    weight in one byte — w_bits/8 bytes at rest, the Fig-3 preload done at
    load time; even w_bits only).

    ``msb_first=True`` marks a *superplane* store: the weight was quantized
    once at ``w_bits`` (= quant.MAX_BITS) and the planes are ordered MSB
    first, so any even effective width ``b <= w_bits`` is served at runtime
    by the first ``b/2`` planes with ``eff_scale(b)`` — no re-quantization,
    no repacking (``prepare_superplane``)."""

    planes: Optional[jax.Array]        # int8 [P, K, N] (or None if packed)
    scale: jax.Array                   # f32 [1, N] (per-channel) or scalar
    w_bits: int
    signed: bool = True
    packed: Optional[jax.Array] = None  # uint8 [K, N]
    msb_first: bool = False             # superplane store (see above)

    @property
    def kn(self) -> Tuple[int, int]:
        if self.planes is not None:
            return self.planes.shape[1], self.planes.shape[2]
        assert self.packed is not None
        return self.packed.shape[0], self.packed.shape[1]

    def get_planes(self) -> jax.Array:
        """Planes in this artifact's declared order (MSB-first iff
        ``msb_first``); unpacks the byte layout on demand."""
        if self.planes is not None:
            return self.planes
        assert self.packed is not None
        planes = unpack_planes(self.packed, self.w_bits, self.signed)
        return planes[::-1] if self.msb_first else planes

    def get_planes_msb(self) -> jax.Array:
        """Planes in MSB-first order regardless of the declared order."""
        planes = self.get_planes()
        return planes if self.msb_first else planes[::-1]

    def eff_scale(self, eff_bits: int) -> jax.Array:
        """Per-channel scale of the ``eff_bits``-truncated weight."""
        return jnp.asarray(quant.nested_scale(self.scale, self.w_bits,
                                              eff_bits))


jax.tree_util.register_dataclass(
    QuantizedWeight, data_fields=["planes", "scale", "packed"],
    meta_fields=["w_bits", "signed", "msb_first"])


def prepare_weight(w: jax.Array, prec: LayerPrecision,
                   packed: bool = False) -> QuantizedWeight:
    """Quantize (per-channel symmetric) + Table-I decompose a float weight
    at a fixed precision.

    Even widths quantize *nested*: the integer code is the LSB-truncation
    of the 8-bit code (``quant.nested_quantize``), so a weight prepared
    natively at any even width is bit-identical to the runtime plane-prefix
    truncation of the superplane store — the property that makes
    fixed-precision engines exact references for runtime tiers.  Odd widths
    (3/5/7) are never plane-prefix-truncatable, so they keep
    round-to-nearest and don't pay the nested scheme's floor bias."""
    cfg = quant.QuantConfig(bits=prec.w_bits, signed=prec.w_signed,
                            per_channel=True, channel_axis=-1)
    if prec.w_bits % 2 == 0:
        q, scale = quant.nested_quantize(w, cfg)
    else:
        q, scale = quant.quantize(w, cfg)
    planes = decompose.decompose_weights(q, prec.w_bits, signed=prec.w_signed)
    if packed and prec.w_bits in (2, 4, 6, 8):
        return QuantizedWeight(planes=None, scale=scale, w_bits=prec.w_bits,
                               signed=prec.w_signed,
                               packed=pack_planes(planes, prec.w_bits))
    return QuantizedWeight(planes=planes, scale=scale, w_bits=prec.w_bits,
                           signed=prec.w_signed)


def prepare_superplane(w: jax.Array, *, signed: bool = True,
                       packed: bool = False) -> QuantizedWeight:
    """Quantize + decompose ONCE at 8 bits into the MSB-first superplane
    store — the single preloaded artifact that serves every even runtime
    width (the paper's preload-once / serve-any-precision dataflow)."""
    cfg = quant.QuantConfig(bits=quant.MAX_BITS, signed=signed,
                            per_channel=True, channel_axis=-1)
    q8, scale = quant.quantize(w, cfg)
    planes_msb = decompose.decompose_superplanes(q8, signed=signed)
    if packed:
        # The byte layout is plane-position-indexed (field c at bits 2c), so
        # it is order-agnostic: pack from the LSB-first view.
        return QuantizedWeight(
            planes=None, scale=scale, w_bits=quant.MAX_BITS, signed=signed,
            packed=pack_planes(planes_msb[::-1], quant.MAX_BITS),
            msb_first=True)
    return QuantizedWeight(planes=planes_msb, scale=scale,
                           w_bits=quant.MAX_BITS, signed=signed,
                           msb_first=True)


def truncate_weight(qw: QuantizedWeight, eff_bits: int) -> QuantizedWeight:
    """Materialize a fixed-precision artifact from a superplane store.

    Equivalent to ``prepare_weight`` at ``eff_bits`` (bit-exact, asserted in
    tests/test_precision_tiers.py) but touches only the stored planes —
    useful for exporting one tier without the float weights."""
    if not qw.msb_first:
        raise ValueError("truncate_weight needs a superplane (msb_first) store")
    n = decompose.num_prefix_planes(eff_bits)
    scale = qw.eff_scale(eff_bits)
    if qw.packed is not None:
        planes_msb = unpack_planes(qw.packed, qw.w_bits, qw.signed)[::-1][:n]
    else:
        assert qw.planes is not None
        planes_msb = qw.planes[:n]
    planes = planes_msb[::-1]
    if qw.packed is not None:
        return QuantizedWeight(planes=None, scale=scale, w_bits=eff_bits,
                               signed=qw.signed,
                               packed=pack_planes(planes, eff_bits))
    return QuantizedWeight(planes=planes, scale=scale, w_bits=eff_bits,
                           signed=qw.signed)


def pack_planes(planes: jax.Array, w_bits: int) -> jax.Array:
    """Pack all 2-bit planes into one uint8 per weight (even w_bits only).

    Plane c occupies bits [2c, 2c+1].  HBM weight bytes become K*N instead of
    P*K*N — and for 2/4-bit, sub-byte-dense relative to int8 storage."""
    assert w_bits in (2, 4, 6, 8)
    p = planes.shape[0]
    acc = jnp.zeros(planes.shape[1:], jnp.uint8)
    for c in range(p):
        field = (planes[c].astype(jnp.int32) & 0x3).astype(jnp.uint8)
        acc = acc | (field << (2 * c))
    return acc


def unpack_planes(packed: jax.Array, w_bits: int,
                  signed: bool = True) -> jax.Array:
    """Inverse of pack_planes (oracle for the packed kernel)."""
    p = decompose.num_planes(w_bits)
    planes = []
    for c in range(p):
        field = ((packed >> (2 * c)) & 0x3).astype(jnp.int32)
        if signed and c == p - 1:
            field = jnp.where(field >= 2, field - 4, field)
        planes.append(field.astype(jnp.int8))
    return jnp.stack(planes)


def _pad_to(x: jax.Array, m: int, axis: int) -> jax.Array:
    r = x.shape[axis] % m
    if r == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, m - r)
    return jnp.pad(x, pad)


def quantize_activations(
        x: jax.Array, a_bits: int, *, signed: bool = True,
        use_pallas: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """Per-row activation quantization.  x: f32 [..., K] -> (int8, scale).

    ``use_pallas=None`` routes to the fused Pallas kernel on TPU and to the
    plain-jnp oracle elsewhere (bit-identical numerics; off-TPU the kernel
    only runs interpreted, which is far slower to trace in model code).
    ``True``/``False`` force the respective path — parity is asserted in
    tests/test_kernel_parity.py."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return act_quant_pallas(x, a_bits=a_bits, signed=signed)
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    q, s = ref.act_quant_ref(x2, bits=a_bits, signed=signed)
    return q.reshape(*lead, k), s.reshape(*lead, 1)


def act_quant_pallas(
        x: jax.Array, *, a_bits: int = 8, signed: bool = True,
        interpret: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """Direct Pallas activation-quant call (padded), for the serving hot path."""
    interpret = _interpret() if interpret is None else interpret
    lead, k = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    bm = act_quant_kernel.block_rows(m, k)
    x2p = _pad_to(x2, bm, 0)
    q, s = act_quant_kernel.act_quant(x2p, bits=a_bits, signed=signed, bm=bm,
                                      interpret=interpret)
    return q[:m].reshape(*lead, k), s[:m].reshape(*lead, 1)


def _group_plane_counts(qw: QuantizedWeight,
                        eff_list: Tuple[int, ...]) -> Tuple[int, ...]:
    """MSB-first plane-prefix depth per group; validates the store serves
    every requested effective width."""
    counts = []
    for eff in eff_list:
        if eff != qw.w_bits and not qw.msb_first:
            raise ValueError(
                f"effective {eff}b from a fixed {qw.w_bits}b weight needs a "
                "superplane (msb_first) store")
        if qw.msb_first:
            counts.append(decompose.num_prefix_planes(eff))
        else:
            counts.append(decompose.num_planes(qw.w_bits, qw.signed))
    return tuple(counts)


def bitserial_matmul_pallas(x_int8: jax.Array, qw: QuantizedWeight, *,
                            eff_bits: Optional[int] = None,
                            row_groups: Optional[Tuple[Tuple[int, int], ...]]
                            = None,
                            interpret: Optional[bool] = None,
                            bm: int = 128, bn: int = 128,
                            bk: int = 128) -> jax.Array:
    """Padded Pallas plane-GEMM: int8 [..., K] x planes -> int32 [..., N].

    ``eff_bits`` < qw.w_bits runtime-truncates a superplane store: the
    packed kernel reads only the MSB byte fields in place, the unpacked
    kernel receives the plane prefix — MXU passes scale with the EFFECTIVE
    width, not the stored one.

    ``row_groups`` (static tuple of ``(rows, eff_bits)``, covering x's
    leading axis) is the mixed-tier decode path: the batch is already
    sorted into contiguous tier groups and ONE group-switching kernel
    (``grouped_matmul``) serves every group from a single grid — per-row
    plane multipliers select each row's plane-prefix depth, so no per-group
    dispatch loop remains (bit-identical to per-group calls).

    ``row_groups`` always counts LEADING-axis rows.  Extra leading dims
    (e.g. the speculative verify window's ``[B, W, K]`` input) flatten to
    ``B*W`` flat rows and each group's row count scales by the static
    ``reps = W`` factor — window positions inherit their slot's tier, so
    the whole ``k+1``-token verify window runs through the same single
    grid as a 1-token decode step."""
    if row_groups is not None:
        if sum(r for r, _ in row_groups) != x_int8.shape[0]:
            raise ValueError(f"row_groups {row_groups} do not cover leading "
                             f"axis {x_int8.shape[0]}")
        interpret = _interpret() if interpret is None else interpret
        k, n = qw.kn
        lead = x_int8.shape[:-1]
        x2 = x_int8.reshape(-1, k)
        m = x2.shape[0]
        reps = m // x_int8.shape[0]       # flat rows per leading row (static)
        counts = _group_plane_counts(qw, tuple(e for _, e in row_groups))
        plane_groups = tuple((rows * reps, p)
                             for (rows, _), p in zip(row_groups, counts))
        mult = jnp.asarray(decompose.prefix_multipliers(plane_groups))
        pmax = int(mult.shape[1])
        bm_eff = min(bm, max(8, m))
        x2 = _pad_to(_pad_to(x2, bm_eff, 0), bk, 1)
        multp = _pad_to(mult, bm_eff, 0)  # zero-multiplier rows stay inert
        if qw.packed is not None:
            wmat = _pad_to(_pad_to(qw.packed, bk, 0), bn, 1)
        else:
            wmat = _pad_to(_pad_to(qw.get_planes_msb()[:pmax], bk, 1), bn, 2)
        out = gmm.grouped_matmul(
            x2, wmat, multp, nplanes=pmax, packed=qw.packed is not None,
            store_planes=decompose.num_planes(qw.w_bits, qw.signed),
            signed=qw.signed, bm=bm_eff, bn=bn, bk=bk, interpret=interpret)
        return out[:m, :n].reshape(*lead, n)
    interpret = _interpret() if interpret is None else interpret
    eff = qw.w_bits if eff_bits is None else eff_bits
    if eff != qw.w_bits and not qw.msb_first:
        raise ValueError(
            f"effective {eff}b from a fixed {qw.w_bits}b weight needs a "
            "superplane (msb_first) store")
    lead = x_int8.shape[:-1]
    k, n = qw.kn
    x2 = x_int8.reshape(-1, k)
    m = x2.shape[0]
    bm_eff = min(bm, max(8, m))
    x2 = _pad_to(_pad_to(x2, bm_eff, 0), bk, 1)
    if qw.packed is not None:
        packed = _pad_to(_pad_to(qw.packed, bk, 0), bn, 1)
        out = bsm.packed_bitserial_matmul(
            x2, packed, w_bits=qw.w_bits, eff_bits=eff, signed=qw.signed,
            bm=bm_eff, bn=bn, bk=bk, interpret=interpret)
    else:
        assert qw.planes is not None
        planes = qw.planes
        if qw.msb_first:
            planes = planes[: decompose.num_prefix_planes(eff)]
        planes = _pad_to(_pad_to(planes, bk, 1), bn, 2)
        out = bsm.bitserial_matmul(x2, planes, w_bits=eff,
                                   msb_first=qw.msb_first,
                                   bm=bm_eff, bn=bn, bk=bk,
                                   interpret=interpret)
    return out[:m, :n].reshape(*lead, n)


def _quantize_activations_rows(
        x: jax.Array, row_groups: RowGroups, perm: Optional[jax.Array],
        use_pallas: Optional[bool]) -> Tuple[jax.Array, jax.Array]:
    """Mixed-width per-row activation quantization (signed), full batch.

    Quantizes the UN-permuted batch in one pass — each row at its own
    ``a_bits``, carried by a per-row f32 qmax — then gathers codes and
    scales by ``perm``.  Row-wise bit-identical to the per-config
    :func:`quantize_activations` (exact max reduction, same f32 divisor),
    so the PR-3 bitwise-stability contract holds with ONE dispatch for any
    mix of activation widths."""
    if use_pallas is None:
        use_pallas = _on_tpu()
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
    if use_pallas:
        m = x2.shape[0]
        bm = act_quant_kernel.block_rows(m, k)
        x2p = _pad_to(x2, bm, 0)
        # Real qmax is always >= 1, so this only lifts zero padding rows.
        qmaxp = jnp.maximum(_pad_to(qmax_full, bm, 0), 1.0)
        q, s = act_quant_kernel.act_quant_rows(x2p, qmaxp, bm=bm,
                                               interpret=_interpret())
        q, s = q[:m], s[:m]
    else:
        q, s = ref.act_quant_rows_ref(x2, qmax_full)
    qr, sr = q.reshape(*lead, k), s.reshape(*lead, 1)
    if perm is not None:
        qr = jnp.take(qr, perm, axis=0)
        sr = jnp.take(sr, perm, axis=0)
    return qr, sr


def quantize_activations_grouped(
        x: jax.Array, row_groups: RowGroups, perm: Optional[jax.Array], *,
        act_quants: Optional[ActQuants] = None,
        use_pallas: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """Activation quantization for a grouped batch, returned PERMUTED
    (group-sorted).  Always quantizes the full un-permuted batch (the PR-3
    bitwise-stability contract) and only gathers results.

    One distinct (a_bits, a_signed) -> a single plain quantization; mixed
    widths (all signed) -> ONE per-row-range pass.  ``act_quants`` is an
    optional cache shared by projections reading the SAME input tensor
    (q/k/v, gate/up): the second caller reuses the first caller's codes —
    identical computation, so sharing is exact."""
    if act_quants is None:
        act_quants = {}
    configs = tuple(dict.fromkeys((g.a_bits, g.a_signed)
                                  for _, g in row_groups))
    if len(configs) == 1:
        a_bits, a_signed = configs[0]
        key: Any = ("uniform", a_bits, a_signed)
        if key not in act_quants:
            act_quants[key] = quantize_activations(
                x.astype(jnp.float32), a_bits, signed=a_signed,
                use_pallas=use_pallas)
        q, s = act_quants[key]
        if perm is not None:
            q = jnp.take(q, perm, axis=0)
            s = jnp.take(s, perm, axis=0)
        return q, s
    if not all(g.a_signed for _, g in row_groups):
        raise ValueError("mixed activation widths fuse only for signed "
                         "activations (per-row qmin = -qmax - 1)")
    key = ("rows",) + tuple((rows, g.a_bits) for rows, g in row_groups)
    if key not in act_quants:
        act_quants[key] = _quantize_activations_rows(x, row_groups, perm,
                                                     use_pallas)
    return act_quants[key]


def fused_decode_linear(x: jax.Array, qw: QuantizedWeight,
                        row_groups: RowGroups, perm: Optional[jax.Array], *,
                        act_quants: Optional[ActQuants] = None,
                        pre_quant: Optional[Tuple[jax.Array,
                                                  jax.Array]] = None,
                        out_dtype: Any = None,
                        interpret: Optional[bool] = None,
                        bm: int = 128, bn: int = 128,
                        bk: int = 128) -> jax.Array:
    """The fused mixed-tier decode hot path, in two dispatches:

      1. ONE activation quantization over the full un-permuted batch
         (per-row ranges when groups mix ``a_bits``; shared across
         projections of the same input via ``act_quants``);
      2. ONE group-switching plane-prefix GEMM whose flush step applies
         both scales (``grouped_dequant_matmul``) — the accumulator never
         leaves VMEM unscaled.

    ``pre_quant`` supplies already-quantized PERMUTED ``(codes, scales)``
    and skips step 1 — the tensor-parallel path quantizes once with a
    mesh-shared range and all-gathers the codes, then lands here so shards
    reuse this exact GEMM + dequant epilogue.

    Returns results in PERMUTED (group-sorted) order, like
    ``matmul(row_groups=)``; bit-identical to the per-group path: integer
    plane combination is exact, and the f32 dequant applies the same values
    in the same order as ``_dequant_gemm``."""
    out_dtype = x.dtype if out_dtype is None else out_dtype
    backends = tuple(dict.fromkeys(g.backend for _, g in row_groups))
    if len(backends) != 1 or backends[0] not in ("decomposed", "pallas"):
        raise ValueError("fused grouped matmul needs one integer backend "
                         f"across groups, got {backends}")
    backend = backends[0]
    if pre_quant is not None:
        x_q, x_s = pre_quant
    else:
        x_q, x_s = quantize_activations_grouped(x, row_groups, perm,
                                                act_quants=act_quants)
    k, n = qw.kn
    lead = x_q.shape[:-1]
    reps = 1
    for d in lead[1:]:
        reps *= d
    eff_list = tuple(min(g.w_bits, qw.w_bits) for _, g in row_groups)
    counts = _group_plane_counts(qw, eff_list)
    plane_groups = tuple((rows * reps, p)
                         for (rows, _), p in zip(row_groups, counts))
    mult = jnp.asarray(decompose.prefix_multipliers(plane_groups))
    pmax = int(mult.shape[1])
    # Per-ROW weight scale: each group's effective per-channel scale
    # broadcast over its rows (an exact power-of-two multiple of the stored
    # scale), so one grid dequantizes every tier correctly.
    ws = jnp.concatenate([
        jnp.broadcast_to(
            jnp.asarray(qw.eff_scale(eff) if eff != qw.w_bits else qw.scale,
                        jnp.float32).reshape(1, -1),
            (rows * reps, n))
        for (rows, _), eff in zip(row_groups, eff_list)], axis=0)
    x2 = x_q.reshape(-1, k)
    s2 = x_s.reshape(-1, 1)
    if backend == "decomposed":
        acc = decompose.decomposed_matmul_multipliers(
            x2, qw.get_planes_msb()[:pmax], mult)
        out = (acc.astype(jnp.float32) * s2 * ws).astype(out_dtype)
        return out.reshape(*lead, n)
    interpret = _interpret() if interpret is None else interpret
    m = x2.shape[0]
    bm_eff = min(bm, max(8, m))
    x2p = _pad_to(_pad_to(x2, bm_eff, 0), bk, 1)
    multp = _pad_to(mult, bm_eff, 0)
    s2p = _pad_to(s2, bm_eff, 0)
    wsp = _pad_to(_pad_to(ws, bm_eff, 0), bn, 1)
    if qw.packed is not None:
        wmat = _pad_to(_pad_to(qw.packed, bk, 0), bn, 1)
    else:
        wmat = _pad_to(_pad_to(qw.get_planes_msb()[:pmax], bk, 1), bn, 2)
    out = gmm.grouped_dequant_matmul(
        x2p, wmat, multp, s2p, wsp, nplanes=pmax,
        packed=qw.packed is not None,
        store_planes=decompose.num_planes(qw.w_bits, qw.signed),
        signed=qw.signed, out_dtype=out_dtype,
        bm=bm_eff, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n].reshape(*lead, n)


def matmul(x: jax.Array, w: Optional[jax.Array], prec: LayerPrecision, *,
           qw: Optional[QuantizedWeight] = None,
           a_signed: Optional[bool] = None,
           row_groups: Optional[RowGroups] = None,
           perm: Optional[jax.Array] = None,
           fused: Optional[bool] = None,
           act_quants: Optional[ActQuants] = None) -> jax.Array:
    """The framework's matmul: y = x @ w under a mixed-precision policy.

    x: f32/bf16 [..., K].  w: float [K, N] (dense / fake_quant) — for the
    integer backends pass ``qw`` (prepared planes); if absent it is derived
    from ``w`` on the fly (fine under jit: constant-folded for frozen weights).

    ``row_groups`` (static tuple of ``(rows, LayerPrecision)``) is the
    mixed-tier decode-batch path: the batch's rows, viewed through the
    (traced) permutation ``perm`` (identity if None), form contiguous tier
    groups; every group runs at ITS (w_bits, a_bits) against the shared
    superplane store ``qw``, and results come back IN PERMUTED ORDER (the
    caller inverts the permutation).  Activation quantization runs on the
    full un-permuted batch — so every row's codes AND scales are bitwise
    identical to a tier-homogeneous dispatch (see :func:`_integer_matmul`
    for why that matters).  ``row_groups`` must be static (it keys the jit
    trace); ``prec`` is ignored when it is given.

    ``fused`` selects the grouped implementation: ``None`` (default) fuses
    whenever eligible (one integer backend, signed activations), ``False``
    forces the per-group reference loop, ``True`` asserts eligibility.
    ``act_quants`` optionally shares activation quantization between
    projections of the same input (exact; see
    :func:`quantize_activations_grouped`).
    """
    if row_groups is not None:
        if qw is None:
            raise ValueError("row_groups needs a prepared weight (qw)")
        total = sum(r for r, _ in row_groups)
        if total != x.shape[0]:
            raise ValueError(f"row_groups cover {total} rows, x leading "
                             f"axis is {x.shape[0]}")
        if len(row_groups) == 1:
            y = matmul(x, None, row_groups[0][1], qw=qw)
            # Keep the contract: grouped results come back in PERMUTED
            # order (gathering finished rows is exact).
            return y if perm is None else jnp.take(y, perm, axis=0)
        eligible = (
            len({g.backend for _, g in row_groups}) == 1
            and row_groups[0][1].backend in ("decomposed", "pallas")
            and all(g.a_signed for _, g in row_groups))
        use_fused = eligible if fused is None else fused
        if use_fused:
            # Raises with the precise reason if fused=True yet ineligible.
            return fused_decode_linear(x, qw, row_groups, perm,
                                       act_quants=act_quants,
                                       out_dtype=x.dtype)
        # Per-group reference path: one full-batch activation quantization
        # per distinct a-config, on the UN-permuted x (bitwise identical to
        # the homogeneous path), then one plane-prefix GEMM per group.
        quants: Dict[Tuple[int, bool], Tuple[jax.Array, jax.Array]] = {}
        for _, gprec in row_groups:
            gkey = (gprec.a_bits, gprec.a_signed)
            if gkey not in quants:
                q, s = quantize_activations(x.astype(jnp.float32),
                                            gprec.a_bits,
                                            signed=gprec.a_signed)
                if perm is not None:
                    q = jnp.take(q, perm, axis=0)
                    s = jnp.take(s, perm, axis=0)
                quants[gkey] = (q, s)
        outs = []
        off = 0
        for rows, gprec in row_groups:
            x_q, x_s = quants[(gprec.a_bits, gprec.a_signed)]
            sl = slice(off, off + rows)
            outs.append(_dequant_gemm(x_q[sl], x_s[sl], qw, gprec, x.dtype))
            off += rows
        return jnp.concatenate(outs, axis=0)
    a_signed = prec.a_signed if a_signed is None else a_signed
    backend = prec.backend

    if backend == "dense":
        assert w is not None
        return jnp.matmul(x, w.astype(x.dtype))

    if backend == "fake_quant":
        assert w is not None
        wcfg = quant.QuantConfig(bits=prec.w_bits, signed=prec.w_signed,
                                 per_channel=True, channel_axis=-1)
        acfg = quant.QuantConfig(bits=prec.a_bits, signed=a_signed,
                                 per_channel=False)
        # Quant math in f32, but cast operands back to the compute dtype
        # BEFORE the matmul: otherwise XLA all-gathers the fake-quantized
        # weights/activations in f32 (2x collective + HBM traffic) and runs
        # f32 matmuls (§Perf iteration 1 — confirmed 1.9x memory-term win).
        wq = quant.fake_quant(w.astype(jnp.float32), wcfg).astype(x.dtype)
        xq = quant.fake_quant(x.astype(jnp.float32), acfg).astype(x.dtype)
        return jnp.matmul(xq, wq)

    if qw is None:
        assert w is not None
        qw = prepare_weight(w.astype(jnp.float32), prec)
    return _integer_matmul(x, qw, prec, a_signed)


def _integer_matmul(x: jax.Array, qw: QuantizedWeight, prec: LayerPrecision,
                    a_signed: bool) -> jax.Array:
    """Shared integer path: act-quant + plane-prefix GEMM + dequant.

    Bitwise-stability note (the mixed-tier token-identity contract): the
    grouped path in :func:`matmul` must produce EXACTLY these bits per row.
    Integer codes and GEMMs are exact, but the activation scales are
    continuous — if a group quantized a sliced or gathered sub-batch, XLA
    would re-fuse the upstream normalization into that group's kernel and
    its f32 reductions could round one ulp differently.  The grouped path
    therefore quantizes the full un-permuted batch with this same graph and
    only gathers the RESULTS."""
    x_q, x_s = quantize_activations(x.astype(jnp.float32), prec.a_bits,
                                    signed=a_signed)
    return _dequant_gemm(x_q, x_s, qw, prec, x.dtype)


def _dequant_gemm(x_q: jax.Array, x_s: jax.Array, qw: QuantizedWeight,
                  prec: LayerPrecision, out_dtype: Any) -> jax.Array:
    """Plane-prefix GEMM on quantized activations + scale-out.

    Runtime precision: the effective width is the POLICY's w_bits, the
    stored width is the artifact's.  A superplane store serves any even
    effective width below its stored width via plane-prefix truncation."""
    backend = prec.backend
    eff_bits = min(prec.w_bits, qw.w_bits)
    if eff_bits != qw.w_bits and not qw.msb_first:
        raise ValueError(
            f"policy asks {eff_bits}b from a fixed {qw.w_bits}b weight; "
            "runtime truncation needs a superplane store "
            "(ops.prepare_superplane)")
    if backend == "decomposed":
        planes = qw.get_planes()
        if qw.msb_first:
            planes = planes[: decompose.num_prefix_planes(eff_bits)][::-1]
        acc = jnp.asarray(decompose.decomposed_matmul(x_q, planes, eff_bits))
    elif backend == "pallas":
        acc = bitserial_matmul_pallas(x_q, qw, eff_bits=eff_bits)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    w_s = qw.eff_scale(eff_bits) if eff_bits != qw.w_bits else qw.scale
    return (acc.astype(jnp.float32) * x_s * w_s).astype(out_dtype)


def dequant_matmul(x_q: jax.Array, x_s: jax.Array, qw: QuantizedWeight,
                   prec: LayerPrecision, out_dtype: Any) -> jax.Array:
    """Public pre-quantized entry to the plane-prefix GEMM + dequant.

    Identical to the tail of :func:`_integer_matmul` — the tensor-parallel
    path calls this after quantizing with a mesh-shared range and gathering
    codes across shards, so sharded and unsharded decode run the very same
    GEMM/dequant graph per row."""
    return _dequant_gemm(x_q, x_s, qw, prec, out_dtype)


def count_pallas_calls(jaxpr: Any) -> int:
    """Count ``pallas_call`` equations in a (Closed)Jaxpr, recursing into
    sub-jaxprs (scan/pjit/cond bodies) — the dispatch-count observability
    behind ``EngineStats.decode_dispatches``: a fused mixed-tier decode
    step's count is CONSTANT in the number of tier groups."""
    core = getattr(jaxpr, "jaxpr", jaxpr)
    count = 0
    for eqn in core.eqns:
        if eqn.primitive.name == "pallas_call":
            count += 1
        for v in eqn.params.values():
            count += _count_pallas_in_param(v)
    return count


def _count_pallas_in_param(v: Any) -> int:
    if isinstance(v, (tuple, list)):
        return sum(_count_pallas_in_param(u) for u in v)
    if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
        return count_pallas_calls(v)
    return 0
