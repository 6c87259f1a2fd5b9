"""Pallas TPU kernel: fused per-row activation quantization.

The accelerator receives activations already quantized (serial bit feed);
on TPU the quantize step is a VPU pass we fuse into one kernel so the f32
activation tensor is read from HBM exactly once, emitting int8 + per-row
scale.  Rows are the flattened (batch x seq) axis.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Bytes of the f32 input block one grid step holds in VMEM.  The block keeps
# whole rows (the amax reduction runs over K), so its row count shrinks as K
# grows: at d_ff 12800 a 128-row block (and its temporaries) overflows the
# TPU's scoped VMEM, while 2 MiB leaves room for double buffering.
BLOCK_BYTES: int = 2 << 20


def block_rows(m: int, k: int) -> int:
    """Rows per grid step for an [m, k] f32 input: every row whole, at most
    128 rows, at most :data:`BLOCK_BYTES` of input.  Callers pad ``m`` to a
    multiple of it.  Rows quantize independently, so the choice never
    changes a bit of the result."""
    cap = max(8, min(128, BLOCK_BYTES // (4 * k) // 8 * 8))
    return m if m <= cap else cap


def _kernel(x_ref: Any, q_ref: Any, s_ref: Any, *, qmin: int,
            qmax: int) -> None:
    x = x_ref[...]
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    # Explicit f32 reciprocal-multiply: `amax / qmax` with a CONSTANT qmax
    # is strength-reduced by XLA to `amax * (1/qmax)` under jit but stays a
    # true division eagerly (and when qmax is a traced per-row array, as in
    # _rows_kernel) — a 1-ulp scale drift that flips quant codes.  Writing
    # the reciprocal out pins every variant to the same bits.
    scale = jnp.maximum(amax, 1e-8) * (jnp.float32(1.0) / jnp.float32(qmax))
    q = jnp.clip(jnp.round(x / scale), qmin, qmax)
    q_ref[...] = q.astype(q_ref.dtype)
    s_ref[...] = scale.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "signed", "bm", "interpret"))
def act_quant(x: jax.Array, *, bits: int = 8, signed: bool = True,
              bm: int = 128,
              interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Per-row symmetric quantization. x: f32 [M, K] -> (int8 [M, K], f32 [M, 1]).

    M must tile by bm (ops.py pads to :func:`block_rows`); K is kept whole
    in VMEM (row reduction)."""
    m, k = x.shape
    assert m % bm == 0, (m, bm)
    qmax = (1 << (bits - 1)) - 1 if signed else (1 << bits) - 1
    qmin = -(1 << (bits - 1)) if signed else 0
    qdtype = jnp.int8 if signed else jnp.uint8

    q, s = pl.pallas_call(
        functools.partial(_kernel, qmin=qmin, qmax=qmax),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), qdtype),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return q, s


def _rows_kernel(x_ref: Any, qmax_ref: Any, q_ref: Any, s_ref: Any) -> None:
    x = x_ref[...]
    qmax = qmax_ref[...]                      # f32 [bm, 1], per-row
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    # Same reciprocal-multiply form as _kernel (see comment there); 1/qmax
    # is an exact-IEEE f32 division, matching the constant XLA folds.
    scale = jnp.maximum(amax, 1e-8) * (jnp.float32(1.0) / qmax)
    q = jnp.clip(jnp.round(x / scale), -qmax - 1.0, qmax)
    q_ref[...] = q.astype(q_ref.dtype)
    s_ref[...] = scale.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def act_quant_rows(x: jax.Array, qmax: jax.Array, *, bm: int = 128,
                   interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Per-row symmetric quantization with a PER-ROW signed range.

    The mixed-tier fused decode path quantizes rows of different ``a_bits``
    in ONE kernel: ``qmax`` f32 [M, 1] carries each row's ``2^(b-1) - 1``
    (exact in f32), ``qmin`` is ``-qmax - 1``.  Row-wise this is the exact
    computation of :func:`act_quant` at that row's width — amax is an exact
    max reduction and the divisor is the same f32 value — so results are
    bit-identical to per-width calls.  x: f32 [M, K] ->
    (int8 [M, K], f32 [M, 1]).  Padding rows should carry qmax=1."""
    m, k = x.shape
    assert m % bm == 0, (m, bm)
    assert qmax.shape == (m, 1), (qmax.shape, m)

    q, s = pl.pallas_call(
        _rows_kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), jnp.int8),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, qmax)
    return q, s
