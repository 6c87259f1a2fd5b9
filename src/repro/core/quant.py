"""2..8-bit quantization for mixed-precision inference/training.

Symmetric integer quantization (per-tensor or per-channel) matching the
paper's operand format: two's-complement signed or unsigned integers of
2..8 bits (the column signal S selects signed/unsigned).

Fake-quant (QAT) uses the straight-through estimator so the dense bf16
training path learns weights that survive the decomposed integer serving
path bit-exactly.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Quantization spec for one operand of one layer."""

    bits: int = 8
    signed: bool = True          # the paper's per-column signal S
    per_channel: bool = True     # per output-channel scales for weights
    channel_axis: int = -1       # axis holding output channels
    eps: float = 1e-8

    def __post_init__(self):
        if not (2 <= self.bits <= 8):
            raise ValueError(f"bits must be in 2..8, got {self.bits}")

    @property
    def qmin(self) -> int:
        return -(1 << (self.bits - 1)) if self.signed else 0

    @property
    def qmax(self) -> int:
        return (1 << (self.bits - 1)) - 1 if self.signed else (1 << self.bits) - 1


def _reduce_axes(x, channel_axis: int):
    axis = channel_axis % x.ndim
    return tuple(a for a in range(x.ndim) if a != axis)


def compute_scale(x, cfg: QuantConfig):
    """Symmetric scale: max|x| mapped to qmax.  Shape broadcasts against x."""
    if cfg.per_channel and x.ndim > 1:
        axes = _reduce_axes(x, cfg.channel_axis)
        amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    else:
        amax = jnp.max(jnp.abs(x))
    # Reciprocal-multiply, as in kernels/ref.quant_scale: XLA rewrites a
    # division by the constant qmax this way under jit but not eagerly, so
    # writing it out keeps jitted and eager preparation bit-identical.
    return jnp.maximum(amax, cfg.eps) * (jnp.float32(1.0)
                                         / jnp.float32(cfg.qmax))


def quantize(x, cfg: QuantConfig, scale=None):
    """float -> int. Returns (q int8/uint8, scale f32), clipped to the q-range.

    Unsigned configs return uint8 — an unsigned 8-bit code point (<=255)
    does not fit int8 (found by the hypothesis roundtrip property test)."""
    scale = compute_scale(x, cfg) if scale is None else scale
    q = jnp.clip(jnp.round(x / scale), cfg.qmin, cfg.qmax)
    dtype = jnp.int8 if cfg.signed else jnp.uint8
    return q.astype(dtype), scale.astype(jnp.float32)


def dequantize(q, scale):
    return q.astype(jnp.float32) * scale


@jax.custom_vjp
def _ste_round(x):
    return jnp.round(x)


def _ste_round_fwd(x):
    return jnp.round(x), None


def _ste_round_bwd(_, g):
    return (g,)


_ste_round.defvjp(_ste_round_fwd, _ste_round_bwd)


def fake_quant(x, cfg: QuantConfig, scale=None):
    """Quantize-dequantize with a straight-through gradient (QAT building block).

    Out-of-range values clip in the forward pass; the gradient passes through
    only inside the clip range (standard STE-with-clipping)."""
    scale = compute_scale(x, cfg) if scale is None else scale
    x_scaled = x / scale
    # Clip gradient mask: zero grad outside representable range.
    clipped = jnp.clip(x_scaled, cfg.qmin, cfg.qmax)
    q = _ste_round(clipped)
    return q * scale


MAX_BITS = 8   # the superplane store always quantizes weights at this width


def nested_scale(scale, from_bits: int, to_bits: int):
    """Effective scale after truncating ``from_bits - to_bits`` LSBs.

    Exact in f32: the multiplier is a power of two."""
    return scale * float(1 << (from_bits - to_bits))


def truncate_qint(q, from_bits: int, to_bits: int):
    """Drop the LSBs of an integer code: ``q >> (from_bits - to_bits)``.

    This is the *nested* (progressive) refinement relation: the ``to_bits``
    code is an exact bit-prefix of the ``from_bits`` code, so it is what a
    preloaded superplane array computes when only the MSB planes are read.
    The shift is arithmetic for signed codes (int dtypes) and logical for
    unsigned (the uint8 storage is widened first), i.e. floor rounding —
    the truncated code is biased low by up to one effective LSB, unlike a
    fresh round-to-nearest quantization (documented tradeoff of
    runtime-reconfigurable precision)."""
    shift = from_bits - to_bits
    if shift < 0:
        raise ValueError(f"cannot truncate {from_bits}b up to {to_bits}b")
    return jnp.asarray(q).astype(jnp.int32) >> shift


def nested_quantize(x, cfg: QuantConfig, scale=None):
    """float -> int at ``cfg.bits`` via the nested scheme: round-to-nearest
    once at MAX_BITS, then truncate LSBs.  Returns (q, effective scale).

    Guarantees ``nested_quantize(x, bits=b)`` == LSB-truncation of
    ``nested_quantize(x, bits=MAX_BITS)`` for every b <= MAX_BITS — the
    invariant the runtime plane-prefix serving path relies on."""
    base = dataclasses.replace(cfg, bits=MAX_BITS)
    q8, s8 = quantize(x, base, scale=scale)
    q = truncate_qint(q8, MAX_BITS, cfg.bits)
    dtype = jnp.int8 if cfg.signed else jnp.uint8
    return q.astype(dtype), nested_scale(s8, MAX_BITS, cfg.bits)


def quantize_unsigned_activations(x, bits: int):
    """Post-ReLU activations: unsigned quantization (S=0 column signal)."""
    cfg = QuantConfig(bits=bits, signed=False, per_channel=False)
    return quantize(x, cfg)


def int_matmul_dequant(x_q, w_q, x_scale, w_scale):
    """(x_q @ w_q) * x_scale * w_scale — the integer-domain matmul the
    accelerator performs, mapped back to float."""
    acc = jnp.matmul(x_q.astype(jnp.int32), w_q.astype(jnp.int32))
    return acc.astype(jnp.float32) * x_scale * w_scale
