"""Pallas TPU kernel: plane-decomposed integer GEMM (the paper's MAC array).

TPU-native adaptation of the paper's bit-serial / weight-combination MAC:

  * weight planes (Table-I 2/3-bit chunks, stored as int8) are the
    *stationary* operand — a (P, bk, bn) block resident in VMEM per grid
    step, mirroring "weights preloaded in parallel";
  * the activation tile streams across the K grid axis, mirroring the
    systolic activation flow;
  * per-plane partial sums are combined in the int32 VMEM accumulator with
    static shifts {0,2,4,6} — the 4-column group's shift-add (Fig. 5), fused
    so it costs nothing (the paper needed a slow clock domain for it);
  * each plane product is an int8 x int8 -> int32 MXU pass, so **cost scales
    with weight precision**: 2-bit weights = 1 pass, 8-bit = 4 passes — the
    paper's utilization property on a fixed-width MXU.

Block shapes default to MXU-aligned 128 multiples; the VMEM working set is
  bm*bk (x) + P*bk*bn (w) + bm*bn*4 (acc) bytes.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import decompose


def _kernel(x_ref: Any, w_ref: Any, o_ref: Any, acc_ref: Any, *,
            shifts: Tuple[int, ...], nk: int) -> None:
    """One (i, j, k) grid step: acc += sum_c (x_blk @ w_blk[c]) << shifts[c]."""

    @pl.when(pl.program_id(2) == 0)
    def _init() -> None:
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    acc = acc_ref[...]
    for c, s in enumerate(shifts):  # static plane loop (P in 1..4)
        part = jax.lax.dot_general(
            x, w_ref[c],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        acc = acc + (part << s)
    acc_ref[...] = acc

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush() -> None:
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("w_bits", "msb_first", "bm", "bn", "bk",
                              "interpret"))
def bitserial_matmul(x: jax.Array, w_planes: jax.Array, *, w_bits: int,
                     msb_first: bool = False,
                     bm: int = 128, bn: int = 128, bk: int = 128,
                     interpret: bool = False) -> jax.Array:
    """int32 [M, N] = sum_c (x int8 [M, K] @ w_planes[c] int8 [K, N]) << s_c.

    ``msb_first=False`` (prepared fixed-precision planes): s_c = 2c.
    ``msb_first=True`` (a superplane prefix, runtime-truncated): the caller
    passes the first P' planes of the MSB-first store and the shift table
    flips to s_c = 2(P'-1-c) — the same MXU passes serve any effective
    width with no repacking.  Shapes must tile evenly by (bm, bk, bn); the
    ops.py wrapper pads.
    """
    m, k = x.shape
    p, k2, n = w_planes.shape
    assert k == k2, (k, k2)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    if msb_first:
        shifts = decompose.prefix_shifts(p)
    else:
        shifts = tuple(2 * c for c in range(p))   # LSB-first: 2c per plane
    nk = k // bk

    grid = (m // bm, n // bn, nk)
    out: jax.Array = pl.pallas_call(
        functools.partial(_kernel, shifts=shifts, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((p, bk, bn), lambda i, j, kk: (0, kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x, w_planes)
    return out


def _packed_kernel(x_ref: Any, w_ref: Any, o_ref: Any, acc_ref: Any, *,
                   shifts: Tuple[int, ...], base: int, nk: int,
                   signed: bool) -> None:
    """Packed variant: weight planes packed 4-per-byte (2-bit fields) in one
    uint8 word per 4 planes; unpacked to int8 in VMEM before the MXU pass.

    Beyond-paper optimization: HBM weight traffic scales with w_bits/8 instead
    of P bytes — the decomposition happens at load, exactly where the paper
    does it (weight preload into the array).

    ``base`` > 0 is the runtime-truncation offset: only the fields at bit
    positions >= base (the MSB planes) are read, so one preloaded byte
    serves every even effective width — fewer MXU passes, zero repacking."""

    @pl.when(pl.program_id(2) == 0)
    def _init() -> None:
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    # uint8 [bk, bn], 2-bit fields, plane c at bits 2c; widened to int32
    # because Mosaic cannot lower a shift of a uint8 vector.
    packed = w_ref[...].astype(jnp.int32)
    acc = acc_ref[...]
    nplanes = len(shifts)
    for c, s in enumerate(shifts):
        field = (packed >> (base + 2 * c)) & 0x3  # in [0, 3]
        if signed and c == nplanes - 1:
            # MSB plane: reinterpret 2-bit field as signed [-2, 1].
            field = jnp.where(field >= 2, field - 4, field)
        plane = field.astype(jnp.int8)
        part = jax.lax.dot_general(
            x, plane,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        acc = acc + (part << s)
    acc_ref[...] = acc

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush() -> None:
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("w_bits", "eff_bits", "signed", "bm", "bn",
                              "bk", "interpret"))
def packed_bitserial_matmul(x: jax.Array, w_packed: jax.Array, *, w_bits: int,
                            eff_bits: int | None = None, signed: bool = True,
                            bm: int = 128, bn: int = 128, bk: int = 128,
                            interpret: bool = False) -> jax.Array:
    """Packed-plane GEMM: w_packed uint8 [K, N] holds all 2-bit planes of a
    2/4/6/8-bit weight in one byte (plane c at bit position 2c).

    ``eff_bits`` (default: w_bits) runtime-truncates a wider packed store —
    only the top ``eff_bits/2`` fields are extracted and the shift table is
    rebased, so a single preloaded byte per weight serves any even effective
    width <= w_bits.  Only even w_bits (pure 2-bit-mode schedules) pack this
    way; 3/5/7-bit use the unpacked kernel.  Returns int32 [M, N]."""
    assert w_bits in (2, 4, 6, 8), "packed layout covers 2-bit-mode schedules"
    eff_bits = w_bits if eff_bits is None else eff_bits
    assert eff_bits in (2, 4, 6, 8) and eff_bits <= w_bits, (eff_bits, w_bits)
    m, k = x.shape
    k2, n = w_packed.shape
    assert k == k2
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    shifts = decompose.plane_shifts(eff_bits, signed)
    base = w_bits - eff_bits           # LSB fields below this are dropped
    nk = k // bk

    grid = (m // bm, n // bn, nk)
    out: jax.Array = pl.pallas_call(
        functools.partial(_packed_kernel, shifts=shifts, base=base, nk=nk,
                          signed=signed),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x, w_packed)
    return out
