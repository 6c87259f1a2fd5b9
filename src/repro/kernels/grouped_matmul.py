"""Pallas TPU kernel: ONE group-switching plane-prefix GEMM for mixed-tier
decode batches.

A mixed-tier batch arrives group-sorted: contiguous row runs at effective
widths 8/6/4/2.  The per-group path launches one ``pallas_call`` per run;
this kernel serves ALL runs from one grid — the software analogue of the
paper's bit-serial systolic array, where a single fixed PE array serves
every precision by switching how many weight bit-planes participate and
combining partial sums spatially (Eq. 1 / Fig. 5).

The switch is data, not control flow: a compile-time int32 multiplier table
``mult[r, c] = 4**(P'_r - 1 - c)`` for plane ``c < P'_r`` (else 0), built by
``decompose.prefix_multipliers`` from the static ``(tier, rows)`` layout.
Every grid step walks the widest prefix (``Pmax`` MSB-first planes; one
int8xint8->int32 MXU pass each) and scales plane ``c``'s partial product by
``mult[:, c]`` — an exact integer shift per row, zero for planes beyond the
row's prefix.  Rows of different widths therefore share every MXU pass and
the result is bit-identical to the per-group kernel (integer multiplication
by a power of four is a shift; integer addition is associative).

Both weight layouts ride the same grid:

  * unpacked — int8 [Pmax, K, N] MSB-first plane prefix, plane ``c`` read
    directly;
  * packed — uint8 [K, N] with all four store planes in one byte; MSB-first
    plane ``c`` is byte field ``store_planes - 1 - c`` (group-INDEPENDENT —
    that is what makes one grid serve every width), sign-reinterpreted only
    for the store's top field.

``grouped_matmul`` emits the raw int32 accumulator; ``grouped_dequant_matmul``
additionally applies the per-row activation scale and per-row weight scale in
the flush step (the fused-dequant epilogue), so the accumulator never
leaves VMEM unscaled.

shard_map compatibility (distributed/tp_serve): every operand is either
replicated (the per-row multiplier table, activation codes after the
quantized all-gather) or sharded on a non-contracting dim (weight planes /
packed bytes / scales on N), so the kernel body needs no collectives and a
device's local call computes an exact N-shard of the unsharded result —
the grid never splits a K-reduction across devices.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import decompose

STORE_PLANES: int = 4   # decompose.SUPERPLANE_PLANES — byte fields per weight


def _plane(w_ref: Any, c: int, *, packed: bool, store_planes: int,
           signed: bool) -> jax.Array:
    """Materialize MSB-first plane ``c`` of the weight block (int8 [bk, bn])."""
    if not packed:
        return w_ref[c]
    field_idx = store_planes - 1 - c        # MSB-first plane c <-> byte field
    # Widen before shifting: Mosaic cannot lower a shift of a uint8 vector.
    field = (w_ref[...].astype(jnp.int32) >> (2 * field_idx)) & 0x3
    if signed and field_idx == store_planes - 1:
        # The store's top field is the sign-carrying MSB chunk.
        field = jnp.where(field >= 2, field - 4, field)
    return field.astype(jnp.int8)


def _accumulate(x_ref: Any, w_ref: Any, mult_ref: Any, acc_ref: Any, *,
                nplanes: int, packed: bool, store_planes: int,
                signed: bool) -> None:
    """acc += sum_c (x_blk @ plane_c) * mult[:, c]  (static plane loop)."""
    x = x_ref[...]
    mult = mult_ref[...]
    acc = acc_ref[...]
    for c in range(nplanes):
        plane = _plane(w_ref, c, packed=packed, store_planes=store_planes,
                       signed=signed)
        part = jax.lax.dot_general(
            x, plane,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        acc = acc + part * mult[:, c:c + 1]
    acc_ref[...] = acc


def _kernel(x_ref: Any, w_ref: Any, mult_ref: Any, o_ref: Any, acc_ref: Any,
            *, nplanes: int, nk: int, packed: bool, store_planes: int,
            signed: bool) -> None:
    @pl.when(pl.program_id(2) == 0)
    def _init() -> None:
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate(x_ref, w_ref, mult_ref, acc_ref, nplanes=nplanes,
                packed=packed, store_planes=store_planes, signed=signed)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush() -> None:
        o_ref[...] = acc_ref[...]


def _dequant_kernel(x_ref: Any, w_ref: Any, mult_ref: Any, xs_ref: Any,
                    ws_ref: Any, o_ref: Any, acc_ref: Any, *, nplanes: int,
                    nk: int, packed: bool, store_planes: int,
                    signed: bool) -> None:
    @pl.when(pl.program_id(2) == 0)
    def _init() -> None:
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate(x_ref, w_ref, mult_ref, acc_ref, nplanes=nplanes,
                packed=packed, store_planes=store_planes, signed=signed)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush() -> None:
        # Fused dequant epilogue: int32 acc -> out dtype with per-row
        # activation scale x per-row weight scale, entirely in VMEM.
        scaled = acc_ref[...].astype(jnp.float32) * xs_ref[...] * ws_ref[...]
        o_ref[...] = scaled.astype(o_ref.dtype)


def _w_spec(nplanes: int, packed: bool, bn: int, bk: int) -> pl.BlockSpec:
    if packed:
        return pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
    return pl.BlockSpec((nplanes, bk, bn), lambda i, j, kk: (0, kk, j))


def _check_shapes(x: jax.Array, w: jax.Array, mult: jax.Array, nplanes: int,
                  packed: bool, bm: int, bn: int, bk: int) -> tuple[int, int]:
    m, k = x.shape
    if packed:
        k2, n = w.shape
    else:
        p, k2, n = w.shape
        assert p == nplanes, (p, nplanes)
    assert k == k2, (k, k2)
    assert mult.shape == (m, nplanes), (mult.shape, m, nplanes)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    return m, n


@functools.partial(
    jax.jit, static_argnames=("nplanes", "packed", "store_planes", "signed",
                              "bm", "bn", "bk", "interpret"))
def grouped_matmul(x: jax.Array, w: jax.Array, mult: jax.Array, *,
                   nplanes: int, packed: bool = False,
                   store_planes: int = STORE_PLANES, signed: bool = True,
                   bm: int = 128, bn: int = 128, bk: int = 128,
                   interpret: bool = False) -> jax.Array:
    """int32 [M, N] = sum_c (x @ plane_c) * mult[:, c]  — one kernel for a
    whole mixed-width batch.

    x: int8 [M, K] group-sorted activations; w: int8 [nplanes, K, N]
    MSB-first plane prefix (unpacked) or uint8 [K, N] (packed store);
    mult: int32 [M, nplanes] from ``decompose.prefix_multipliers`` (rows
    beyond a group's prefix weigh 0).  Shapes must tile by (bm, bk, bn);
    the ops.py wrapper pads (zero multiplier rows keep padding inert).
    """
    m, n = _check_shapes(x, w, mult, nplanes, packed, bm, bn, bk)
    k = x.shape[1]
    nk = k // bk
    return pl.pallas_call(
        functools.partial(_kernel, nplanes=nplanes, nk=nk, packed=packed,
                          store_planes=store_planes, signed=signed),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            _w_spec(nplanes, packed, bn, bk),
            pl.BlockSpec((bm, nplanes), lambda i, j, kk: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x, w, mult)


@functools.partial(
    jax.jit, static_argnames=("nplanes", "packed", "store_planes", "signed",
                              "out_dtype", "bm", "bn", "bk", "interpret"))
def grouped_dequant_matmul(x: jax.Array, w: jax.Array, mult: jax.Array,
                           x_scale: jax.Array, w_scale: jax.Array, *,
                           nplanes: int, packed: bool = False,
                           store_planes: int = STORE_PLANES,
                           signed: bool = True, out_dtype: Any = jnp.bfloat16,
                           bm: int = 128, bn: int = 128, bk: int = 128,
                           interpret: bool = False) -> jax.Array:
    """``grouped_matmul`` with the dequant epilogue fused into the flush:
    out [M, N] = (acc.astype(f32) * x_scale * w_scale).astype(out_dtype).

    x_scale: f32 [M, 1] per-row activation scale; w_scale: f32 [M, N]
    per-ROW weight scale rows (each row is its group's effective scale —
    ``qw.eff_scale`` broadcast by the static layout), so rows of different
    tiers dequantize correctly inside one grid.
    """
    m, n = _check_shapes(x, w, mult, nplanes, packed, bm, bn, bk)
    assert x_scale.shape == (m, 1), (x_scale.shape, m)
    assert w_scale.shape == (m, n), (w_scale.shape, m, n)
    k = x.shape[1]
    nk = k // bk
    return pl.pallas_call(
        functools.partial(_dequant_kernel, nplanes=nplanes, nk=nk,
                          packed=packed, store_planes=store_planes,
                          signed=signed),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            _w_spec(nplanes, packed, bn, bk),
            pl.BlockSpec((bm, nplanes), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x, w, mult, x_scale, w_scale)
