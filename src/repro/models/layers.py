"""Model building blocks (pure-JAX, functional): norms, RoPE, quantized
linear, flash attention (online-softmax, memory-bounded), KV caches.

Every matmul routes through ``kernels.ops.matmul`` under the layer's
``LayerPrecision`` from the model's ``PrecisionPolicy`` — the paper's
flexible 2..8-bit precision scaling as a first-class model feature.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.policy import LayerPrecision, PrecisionPolicy, PrecisionSchedule
from repro.distributed import tp_serve
from repro.distributed.sharding import shard
from repro.kernels import kv_attention, ops


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Per-call execution context threaded through the model.

    Precision comes from ONE of two sources: a fixed ``policy`` (prepare-time
    precision, the classic path) or a ``schedule`` + tier information
    (runtime-reconfigurable serving over one superplane weight store).  In
    schedule mode there are again two shapes:

    * ``tier`` — the whole batch runs at one named tier
      (:meth:`for_tier`; the tier name is a JIT-STATIC argument of the
      engine's dispatch functions);
    * ``groups`` + ``perm``/``inv_perm`` — a mixed-tier decode batch
      (:meth:`for_groups`): ``groups`` is a STATIC tuple of
      ``(tier_name, rows)`` describing contiguous tier-sorted slot groups
      (it keys the jit trace), while ``perm``/``inv_perm`` are TRACED
      int32 [B] arrays mapping batch rows into/out of that sorted order
      (they change per step without retracing).  Every projection then
      runs the grouped path (see :func:`linear`): with ``fused`` (default)
      ONE group-switching plane-prefix GEMM serves all groups
      (``ops.fused_decode_linear``); ``fused=False`` keeps the per-group
      dispatch loop as the bit-identical reference.
    """

    policy: PrecisionPolicy
    mode: str = "train"                 # train | serve
    deterministic: bool = True
    # Dropless MoE: capacity = T (no token dropping).  Exact but wasteful;
    # used for serving parity and small-scale tests.  Training uses the
    # capacity-factor path (standard token-choice with dropping).
    moe_dropless: bool = False
    schedule: Optional[PrecisionSchedule] = None
    tier: Optional[str] = None          # active tier name (schedule mode)
    groups: Optional[tuple] = None      # STATIC ((tier_name, rows), ...)
    perm: Optional[Any] = None          # TRACED int32 [B]: tier-sorted order
    inv_perm: Optional[Any] = None      # TRACED int32 [B]: inverse of perm
    fused: bool = True                  # one-kernel mixed-tier grouped GEMMs
    # Tensor-parallel context (a static tp_serve.TPConfig), set only INSIDE
    # the engine's shard_map body: params arrive as this device's shards,
    # attention sees local head counts, and o/down projections take the
    # quantized-gather path.  None (default) = the unsharded graph.
    tp: Optional[Any] = None

    def prec(self, name: str) -> LayerPrecision:
        if self.schedule is not None:
            return self.schedule.lookup(name, self.tier)
        return self.policy.lookup(name)

    def for_tier(self, tier: Optional[str]) -> "Runtime":
        """This runtime with the active tier swapped (no-op sans schedule)."""
        if self.schedule is None:
            return self
        return dataclasses.replace(self, tier=tier, groups=None, perm=None,
                                   inv_perm=None)

    def for_groups(self, groups, perm) -> "Runtime":
        """This runtime serving a mixed-tier batch.

        ``groups``: static tuple of ``(tier_name, rows)`` (tier-sorted,
        contiguous, covering the batch).  ``perm``: traced int32 [B] with
        ``perm[i]`` = the batch row that sorted position ``i`` reads from;
        the inverse permutation is derived here (inside the trace)."""
        if self.schedule is None:
            raise ValueError("mixed-tier groups need a PrecisionSchedule")
        return dataclasses.replace(self, tier=None, groups=tuple(groups),
                                   perm=perm, inv_perm=jnp.argsort(perm))

    @property
    def group_batch(self) -> int:
        """Total rows covered by ``groups`` (the slot-batch size)."""
        return sum(n for _, n in self.groups)


# ---------------------------------------------------------------- init utils
def dense_init(key, in_dim: int, out_dim: int, dtype=jnp.bfloat16):
    scale = 1.0 / math.sqrt(in_dim)
    return {"w": jax.random.uniform(key, (in_dim, out_dim), jnp.float32,
                                    -scale, scale).astype(dtype)}


def _serve_backend(prec: LayerPrecision) -> LayerPrecision:
    """Prepared weights only run on the integer serving backends."""
    return prec.with_backend(
        prec.backend if prec.backend in ("decomposed", "pallas")
        else "decomposed")


def linear(params, x, rt: Runtime, name: str, *,
           act_quants: Optional[Dict[Any, Any]] = None):
    """y = x @ w under the mixed-precision policy (w may be a prepared
    QuantizedWeight for the serving path).

    Under a mixed-tier runtime (``rt.groups`` set) every prepared-weight
    matmul takes the per-row-group path: gather batch rows into tier-sorted
    order (``rt.perm``), run the grouped plane-prefix GEMM (one fused
    group-switching kernel when ``rt.fused``, else one GEMM per contiguous
    group) at each group's (w_bits, a_bits), and scatter back
    (``rt.inv_perm``).  The leading axis of ``x`` must be the slot-batch
    axis — true for every projection in the decode path (attention/MLP/SSM
    projections, per-expert MoE FFNs after the per-sequence dispatch, and
    the LM head).

    ``act_quants`` is a per-input activation-quant cache: projections that
    read the SAME tensor (q/k/v, gate/up) pass one shared dict so the batch
    is quantized once per distinct config instead of once per projection —
    identical computation, so sharing is exact.

    Every op it adds lies under the ``linear`` named scope."""
    with jax.named_scope("linear"):
        return _linear(params, x, rt, name, act_quants)


def _linear(params, x, rt: Runtime, name: str, act_quants):
    w = params["w"]
    if isinstance(w, ops.QuantizedWeight):
        if rt.groups is not None:
            if x.shape[0] != rt.group_batch:
                raise ValueError(
                    f"{name}: mixed-tier groups cover {rt.group_batch} slots "
                    f"but x has leading axis {x.shape[0]} — grouped matmuls "
                    "require the slot-batch axis to lead")
            if len(rt.groups) == 1:       # homogeneous layout: no permuting
                tier = rt.groups[0][0]
                prec = _serve_backend(rt.schedule.lookup(name, tier))
                if rt.tp is not None and rt.tp.gathers(name):
                    return tp_serve.gathered_matmul(x, w, prec, tp=rt.tp)
                return ops.matmul(x, None, prec, qw=w)
            row_groups = tuple(
                (n, _serve_backend(rt.schedule.lookup(name, t)))
                for t, n in rt.groups)
            if rt.tp is not None and rt.tp.gathers(name):
                # Feature-sharded input: quantize with the pmax-shared
                # range, gather codes per group at its wire width, run the
                # unchanged group-switching GEMM on the local N-shard.
                yg = tp_serve.gathered_grouped_matmul(x, w, row_groups,
                                                      rt.perm, tp=rt.tp)
                return jnp.take(yg, rt.inv_perm, axis=0)
            # The permutation is applied INSIDE ops.matmul (to the already-
            # quantized codes/scales, keeping scales bitwise stable); the
            # grouped result comes back in sorted order and is scattered
            # back to slot order here.
            yg = ops.matmul(x, None, row_groups[0][1], qw=w,
                            row_groups=row_groups, perm=rt.perm,
                            fused=None if rt.fused else False,
                            act_quants=act_quants)
            return jnp.take(yg, rt.inv_perm, axis=0)
        prec = _serve_backend(rt.prec(name))
        if rt.tp is not None and rt.tp.gathers(name):
            return tp_serve.gathered_matmul(x, w, prec, tp=rt.tp)
        return ops.matmul(x, None, prec, qw=w)
    y = ops.matmul(x, w, rt.prec(name))
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


# --------------------------------------------------------------------- norms
def rmsnorm_init(dim: int, dtype=jnp.bfloat16):
    return {"g": jnp.ones((dim,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    # Variance in f32 (a per-token scalar: sums of squares reduce locally and
    # psum cheaply over a sharded d_model), but the normalized product stays
    # in x.dtype so the d_model all-gather feeding the next matmul moves
    # bf16, not f32 (§Perf iteration 2).
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    return x * (inv.astype(x.dtype)) * params["g"].astype(x.dtype)


def qk_headnorm(params, x, eps: float = 1e-6):
    """Per-head RMSNorm over head_dim (Qwen3-style qk_norm). x: [..., H, Dh]."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["g"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------- RoPE
def rope(x, positions, theta: float = 1e6):
    """Rotary embedding, split-half convention. x: [B, S, H, Dh]."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs     # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------- flash attention
def flash_attention(q, k, v, *, causal: bool = True, block_k: int = 1024,
                    q_offset=0):
    """Online-softmax attention, memory bounded by block_k (the TPU analogue
    of streaming the KV operand; never materializes the [Sq, Sk] matrix).

    q: [B, Sq, H, Dh]; k, v: [B, Sk, KVH, Dh] with H % KVH == 0 (GQA).
    q_offset: absolute position of q[0] (for chunked prefill / decode).
    Returns [B, Sq, H, Dh] in q.dtype.
    """
    with jax.named_scope("attention"):
        b, sq, h, dh = q.shape
        _, sk, kvh, _ = k.shape
        g = h // kvh
        scale = 1.0 / math.sqrt(dh)
        if g > 1:
            # GQA as q-head-major repeat: every tensor keeps the h axis, so
            # TP over "model" survives (a [kvh, g] reshape would break the
            # sharding and replicate the f32 accumulators on every device).
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
        qf = q.transpose(0, 2, 1, 3).astype(jnp.float32)       # [b, h, sq, dh]
        qf = shard(qf, "batch", "model", None, None)

        block_k = min(block_k, sk)
        nb = -(-sk // block_k)
        pad = nb * block_k - sk
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kb = kp.reshape(b, nb, block_k, h, dh).transpose(1, 0, 2, 3, 4)
        vb = vp.reshape(b, nb, block_k, h, dh).transpose(1, 0, 2, 3, 4)
        kpos = jnp.arange(nb * block_k).reshape(nb, block_k)
        qpos = q_offset + jnp.arange(sq)

        neg = jnp.float32(-1e30)

        def body(carry, xs):
            acc, m, l = carry
            kblk, vblk, kp_blk = xs
            s = jnp.einsum("bhqd,bshd->bhqs", qf,
                           kblk.astype(jnp.float32)) * scale
            valid = kp_blk[None, :] < sk
            if causal:
                valid = valid & (qpos[:, None] >= kp_blk[None, :])
            s = jnp.where(valid[None, None], s, neg)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqs,bshd->bhqd", p, vblk.astype(jnp.float32))
            return (acc_new, m_new, l_new), None

        init = (
            jnp.zeros((b, h, sq, dh), jnp.float32),
            jnp.full((b, h, sq), neg),
            jnp.zeros((b, h, sq), jnp.float32),
        )
        (acc, m, l), _ = jax.lax.scan(jax.checkpoint(body), init,
                                      (kb, vb, kpos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ------------------------------------------------------------------ KV cache
# Per-slot KV precision tiers: the decode-memory analogue of the weight
# plane prefix.  A cache runs in one of four storage modes:
#
#   dense     bf16 [B, S, KVH, Dh]                  (kv_bits=None)
#   int8      int8 codes + per-(pos, head) scales   (kv_bits=8)
#   int4      uint8 nibble-packed codes + scales    (kv_bits=4)
#   mixed     ONE uint8 byte-lane arena [B, S, KVH, L] serving bf16 / int8 /
#             int4-packed lanes side by side, with a per-slot tier vector
#             ``kv_bits`` int32 [B] (16 = bf16 passthrough, 8, 4) and shared
#             per-(position, head) scale rows       (kv_bits=(16, 8, 4)-ish
#             tuple of the modes the arena must serve)
#
# The mixed mode is what lets one slot arena serve requests whose
# PrecisionSchedule tier maps to different KV precisions: a slot's lane
# encodes exactly what the homogeneous cache at that kv_bits stores, so
# per-request outputs are bit-identical to a fixed-precision engine.

KV_TIER_BITS = (16, 8, 4)     # bf16 passthrough, int8, int4-packed


def _kv_lane_bytes(bits: int, head_dim: int) -> int:
    """Bytes per (position, head) lane one KV element row needs at a tier."""
    return {16: 2 * head_dim, 8: head_dim, 4: head_dim // 2}[bits]


def _kv_quant(x, bits: int, scale_dtype):
    """Symmetric per-(position, head) KV quantization (int8 codes).

    Wrapped in ``optimization_barrier``s: the scale is CONTINUOUS f32 math,
    and if XLA fuses this subgraph differently per engine (the mixed
    per-slot arena computes several candidate encodings and selects; a
    homogeneous cache computes one), its rounding can drift by one ulp and
    flip a quantization code — breaking the bit-identity between a mixed
    slot and the fixed-precision reference engine at the same kv tier.  The
    barriers pin this subgraph to one compilation in every context."""
    x = jax.lax.optimization_barrier(x.astype(jnp.float32))
    qmax = (1 << (bits - 1)) - 1
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax)
    return jax.lax.optimization_barrier(
        (q.astype(jnp.int8), scale.astype(scale_dtype)))


def _pack_int4(q):
    """int8 codes in [-8, 7] [..., Dh] -> uint8 nibbles [..., Dh//2]
    (element 2i in the low nibble, 2i+1 in the high nibble)."""
    u = jax.lax.bitcast_convert_type(q, jnp.uint8)
    return (u[..., 0::2] & 0xF) | ((u[..., 1::2] & 0xF) << 4)


def _unpack_int4(b):
    """Inverse of :func:`_pack_int4` (sign-extended int8 [..., Dh])."""
    lo = (b & 0xF).astype(jnp.int32)
    hi = ((b >> 4) & 0xF).astype(jnp.int32)
    both = jnp.stack([lo, hi], axis=-1).reshape(*b.shape[:-1], -1)
    return jnp.where(both >= 8, both - 16, both).astype(jnp.int8)


def _bf16_to_bytes(x):
    """bf16 [..., Dh] -> its bit pattern as uint8 [..., 2*Dh] (exact)."""
    by = jax.lax.bitcast_convert_type(x.astype(jnp.bfloat16), jnp.uint8)
    return by.reshape(*by.shape[:-2], -1)


def _bytes_to_bf16(b):
    """Inverse of :func:`_bf16_to_bytes`: uint8 [..., 2*Dh] -> bf16 [..., Dh]."""
    u = b.reshape(*b.shape[:-1], -1, 2)
    return jax.lax.bitcast_convert_type(u, jnp.bfloat16)


@dataclasses.dataclass
class KVCache:
    """Pre-allocated KV cache with PER-SLOT lengths and (optionally)
    PER-SLOT precision tiers — the paper's precision scaling applied to the
    decode memory bottleneck.

    The batch axis is a *slot* axis: every slot tracks its own fill point
    (``length[b]``) and, in mixed mode, its own storage tier
    (``kv_bits[b]``), so a continuous-batching engine can reset/refill one
    slot at a different KV precision while the others keep decoding against
    their caches.  ``kv_bits`` and all array fields are traced data;
    ``modes`` (which tiers the arena serves, descending) is static metadata
    that keys the jit trace."""

    k: jax.Array          # dense/int8: [B, Smax, KVH, Dh]; int4: [..., Dh//2]
    v: jax.Array          # uint8; mixed: uint8 byte lanes [B, Smax, KVH, L]
    k_scale: Optional[jax.Array]   # bf16 [B, Smax, KVH, 1] when quantized
    v_scale: Optional[jax.Array]
    length: jax.Array     # int32 [B] — filled positions per slot
    kv_bits: Optional[jax.Array] = None   # int32 [B] per-slot tier (mixed)
    modes: Optional[tuple] = None         # static tier set, descending

    @property
    def quantized(self) -> bool:
        """Homogeneous int8 storage."""
        return self.k.dtype == jnp.int8

    @property
    def packed4(self) -> bool:
        """Homogeneous int4 nibble-packed storage."""
        return self.k.dtype == jnp.uint8 and self.kv_bits is None

    @property
    def mixed(self) -> bool:
        """Per-slot tiered byte-lane arena."""
        return self.kv_bits is not None

    @property
    def tiers(self) -> tuple:
        """The tier codes the stored lanes serve: ``modes`` for the mixed
        arena, else the one tier of the homogeneous encoding."""
        if self.mixed:
            return self.modes
        if self.quantized:
            return (8,)
        return (4,) if self.packed4 else (16,)

    @property
    def head_dim(self) -> int:
        if self.mixed:
            lanes = self.k.shape[-1]
            return {16: lanes // 2, 8: lanes, 4: 2 * lanes}[self.modes[0]]
        if self.packed4:
            return 2 * self.k.shape[-1]
        return self.k.shape[-1]

    @staticmethod
    def create(batch: int, max_len: int, kv_heads: int, head_dim: int,
               dtype=jnp.bfloat16, kv_bits=None) -> "KVCache":
        """``kv_bits``: None (dense bf16), 8 (int8), 4 (int4-packed), or a
        tuple of tier codes from ``KV_TIER_BITS`` for the mixed per-slot
        arena (lanes sized for the widest tier; per-slot tiers start at the
        widest and are set per admission)."""
        lengths = jnp.zeros((batch,), jnp.int32)
        # Scales in bf16: per-(position, head) f32 scales would cost 50%
        # overhead per device once head_dim is TP-sharded (§Perf decode).
        s = jnp.ones((batch, max_len, kv_heads, 1), jnp.bfloat16)
        if isinstance(kv_bits, (tuple, list)):
            modes = tuple(sorted({int(m) for m in kv_bits}, reverse=True))
            if not modes or any(m not in KV_TIER_BITS for m in modes):
                raise ValueError(f"mixed kv tiers must be from "
                                 f"{KV_TIER_BITS}, got {kv_bits}")
            if head_dim % 2:
                raise ValueError("per-slot KV tiers need an even head_dim")
            lanes = max(_kv_lane_bytes(m, head_dim) for m in modes)
            z = jnp.zeros((batch, max_len, kv_heads, lanes), jnp.uint8)
            tiers = jnp.full((batch,), modes[0], jnp.int32)
            return KVCache(z, z, s, s, lengths, kv_bits=tiers, modes=modes)
        shape = (batch, max_len, kv_heads, head_dim)
        if kv_bits == 8:
            z8 = jnp.zeros(shape, jnp.int8)
            return KVCache(z8, z8, s, s, lengths)
        if kv_bits == 4:
            if head_dim % 2:
                raise ValueError("int4 KV packing needs an even head_dim")
            z4 = jnp.zeros(shape[:-1] + (head_dim // 2,), jnp.uint8)
            return KVCache(z4, z4, s, s, lengths)
        if kv_bits is not None:
            raise ValueError(f"kv_bits must be None, 8, 4 or a tier tuple, "
                             f"got {kv_bits!r}")
        z = jnp.zeros(shape, dtype)
        return KVCache(z, z, None, None, lengths)

    # ------------------------------------------------- mixed-mode encoding
    def _slot_select(self, per_mode, ndim):
        """Select each slot's candidate by its ``kv_bits`` tier code."""
        kv = self.kv_bits.reshape((-1,) + (1,) * (ndim - 1))
        out = per_mode[-1]
        for m, cand in zip(self.modes[:-1], per_mode[:-1]):
            out = jnp.where(kv == m, cand, out)
        return out

    def _encode_mixed(self, x):
        """float [..., Dh] -> (byte lanes [..., L], scale [..., 1]) with
        every slot encoded at its own tier (bit-identical to the
        homogeneous cache at that tier)."""
        lanes = self.k.shape[-1]
        bys, scs = [], []
        for m in self.modes:
            if m == 16:
                by = _bf16_to_bytes(x)
                sc = jnp.ones(x.shape[:-1] + (1,), self.k_scale.dtype)
            else:
                q, sc = _kv_quant(x, m, self.k_scale.dtype)
                by = jax.lax.bitcast_convert_type(q, jnp.uint8) if m == 8 \
                    else _pack_int4(q)
            pad = lanes - by.shape[-1]
            if pad:
                by = jnp.pad(by, [(0, 0)] * (by.ndim - 1) + [(0, pad)])
            bys.append(by)
            scs.append(sc)
        return (self._slot_select(bys, x.ndim),
                self._slot_select(scs, x.ndim))

    def _decode_mixed(self, buf, scale, dtype):
        """byte lanes [..., L] -> dequantized [..., Dh] per slot tier."""
        dh = self.head_dim
        cands = []
        for m in self.modes:
            if m == 16:
                cands.append(_bytes_to_bf16(buf[..., :2 * dh]).astype(dtype))
            elif m == 8:
                q = jax.lax.bitcast_convert_type(buf[..., :dh], jnp.int8)
                cands.append(q.astype(dtype) * scale.astype(dtype))
            else:
                q = _unpack_int4(buf[..., :dh // 2])
                cands.append(q.astype(dtype) * scale.astype(dtype))
        return self._slot_select(cands, cands[0].ndim)

    # --------------------------------------------------------------- writes
    def _lengths_after(self, start, s, new_length):
        if new_length is None:
            return jnp.zeros_like(self.length) + start + s
        return jnp.broadcast_to(new_length, self.length.shape).astype(
            self.length.dtype)

    def _encode(self, x):
        """float K or V rows -> (storage, scale-or-None) for this mode."""
        if self.mixed:
            return self._encode_mixed(x)
        if self.quantized:
            return _kv_quant(x, 8, self.k_scale.dtype)
        if self.packed4:
            q, sc = _kv_quant(x, 4, self.k_scale.dtype)
            return _pack_int4(q), sc
        return x.astype(self.k.dtype), None

    def update(self, k_new, v_new, start, *, new_length=None) -> "KVCache":
        """Insert [B, S_new, KVH, Dh] at position `start` (scalar, traced ok).

        ``new_length`` ([B] or scalar) overrides the resulting per-slot
        lengths — used for right-padded prefill, where ``S_new`` is the
        padded length but only the first ``new_length[b]`` positions of slot
        ``b`` are real tokens."""
        with jax.named_scope("kv_write"):
            idx = (0, start, 0, 0)
            ln = self._lengths_after(start, k_new.shape[1], new_length)
            kq, ks = self._encode(k_new)
            vq, vs = self._encode(v_new)
            k = jax.lax.dynamic_update_slice(self.k, kq, idx)
            v = jax.lax.dynamic_update_slice(self.v, vq, idx)
            if ks is None:
                return dataclasses.replace(self, k=k, v=v, length=ln)
            return dataclasses.replace(
                self, k=k, v=v,
                k_scale=jax.lax.dynamic_update_slice(self.k_scale, ks, idx),
                v_scale=jax.lax.dynamic_update_slice(self.v_scale, vs, idx),
                length=ln)

    def append(self, k_new, v_new, active=None) -> "KVCache":
        """Masked per-slot decode write: one token per slot at that slot's
        own ``length[b]`` (a scatter, not a slice — slots sit at different
        positions).  Slots with ``active[b] == False`` are left untouched:
        neither their K/V rows nor their lengths move, so a finished slot's
        cache is frozen until the scheduler reuses it."""
        with jax.named_scope("kv_write"):
            b = self.k.shape[0]
            if active is None:
                active = jnp.ones((b,), bool)
            # never overflow
            active = active & (self.length < self.k.shape[1])
            idx = jnp.arange(b)
            pos = jnp.clip(self.length, 0, self.k.shape[1] - 1)

            def put(buf, val):
                cur = buf[idx, pos]
                val = jnp.where(active[(...,) + (None,) * (val.ndim - 1)],
                                val.astype(buf.dtype), cur)
                return buf.at[idx, pos].set(val)

            ln = self.length + active.astype(self.length.dtype)
            kq, ks = self._encode(k_new)
            vq, vs = self._encode(v_new)
            k, v = put(self.k, kq[:, 0]), put(self.v, vq[:, 0])
            if ks is None:
                return dataclasses.replace(self, k=k, v=v, length=ln)
            return dataclasses.replace(
                self, k=k, v=v, k_scale=put(self.k_scale, ks[:, 0]),
                v_scale=put(self.v_scale, vs[:, 0]), length=ln)

    def requantize(self, kv_bits_new) -> "KVCache":
        """Re-encode the stored K/V at new per-slot tier codes (mixed mode
        only) — the KV half of mid-stream tier migration.

        ``kv_bits_new`` is a (traced-ok) int32 tier code (16/8/4), scalar or
        [B], broadcast over the slot axis.  The result is exactly what
        :meth:`update` would have stored had the dequantized cache been
        written at the target tier in the first place: dequantize every
        lane at its CURRENT per-slot tier (through :meth:`read`'s barriered
        path), flip the tier codes, re-encode through the same `_encode`
        path.  bf16 -> bf16 is bit-exact (bitcast round-trip); narrowing
        migrations requantize through the shared ``_kv_quant`` so the
        migrated lane is bit-identical to quantizing the dequantized cache
        directly at the target precision.  Lengths and all other slots'
        data are untouched (callers migrate one slot via a slot view)."""
        with jax.named_scope("kv_write"):
            if not self.mixed:
                raise ValueError("requantize() needs the mixed per-slot KV "
                                 "arena (kv_bits tier codes)")
            k, v = self.read(jnp.bfloat16)
            out = dataclasses.replace(
                self, kv_bits=jnp.broadcast_to(
                    jnp.asarray(kv_bits_new, self.kv_bits.dtype),
                    self.kv_bits.shape))
            kq, ks = out._encode(k)
            vq, vs = out._encode(v)
            return dataclasses.replace(out, k=kq, v=vq, k_scale=ks, v_scale=vs)

    def read(self, dtype=jnp.bfloat16):
        """Dequantized (K, V) views of the whole arena.

        Quantized modes return their result through an
        ``optimization_barrier``: the dequant multiply feeds attention
        contractions, and XLA may otherwise fold the per-row scale out of
        the f32 sum (``sum(q*s*x) -> s*sum(q*x)``) in one engine's graph
        but not another's — a one-ulp reassociation that breaks mixed-vs-
        fixed-precision bit-identity.  Dense bf16 reads have no continuous
        scale and stay unbarriered."""
        with jax.named_scope("attention"):
            if self.mixed:
                return jax.lax.optimization_barrier(
                    (self._decode_mixed(self.k, self.k_scale, dtype),
                     self._decode_mixed(self.v, self.v_scale, dtype)))
            if self.quantized:
                k = self.k.astype(dtype) * self.k_scale.astype(dtype)
                v = self.v.astype(dtype) * self.v_scale.astype(dtype)
                return jax.lax.optimization_barrier((k, v))
            if self.packed4:
                k = _unpack_int4(self.k).astype(dtype) \
                    * self.k_scale.astype(dtype)
                v = _unpack_int4(self.v).astype(dtype) \
                    * self.v_scale.astype(dtype)
                return jax.lax.optimization_barrier((k, v))
            return self.k.astype(dtype), self.v.astype(dtype)


jax.tree_util.register_dataclass(
    KVCache, data_fields=["k", "v", "k_scale", "v_scale", "length",
                          "kv_bits"],
    meta_fields=["modes"])


def decode_kernel_engages(max_len: int, heads: int, kv_heads: int,
                          head_dim: int) -> bool:
    """Whether :func:`decode_attention` runs the Pallas kernel
    (``kernels.kv_attention``) for caches of these shapes — the head
    counts a device holds, local ones inside a mesh engine's shard_map:
    on the TPU, for every encoding, when the shapes tile and the scratch
    fits VMEM; otherwise the jnp path."""
    return ops._on_tpu() and kv_attention.tiles_on_tpu(max_len, heads,
                                                       kv_heads, head_dim)


def decode_attention(q, cache: KVCache):
    """Single-step attention against a cache. q: [B, 1, H, Dh].

    On the TPU (:func:`decode_kernel_engages`) one Pallas kernel reads the
    cache as stored: only each slot's filled blocks, dequantized in VMEM at
    the slot's own tier, so no arena-sized bf16 K/V ever exists.  Every
    encoding goes through it there, which keeps a mixed-arena slot
    bit-identical to the homogeneous cache at its tier on the chip.

    Elsewhere: the grouped (kvh, g) einsum form over :meth:`KVCache.read` —
    no K/V repeat, operands stay in the cache dtype (bf16/int8-dequant)
    with f32 accumulation via preferred_element_type, so the big cache
    tensors are never materialized in f32 and the head_dim contraction
    runs sharded (§Perf decode iters)."""
    with jax.named_scope("attention"):
        b, sq, h, dh = q.shape
        if sq == 1 and decode_kernel_engages(cache.k.shape[1], h,
                                             cache.k.shape[2], dh):
            out = kv_attention.kv_decode_attention(
                q.reshape(b, h, dh), cache.k, cache.v, cache.k_scale,
                cache.v_scale, cache.length, cache.kv_bits,
                tiers=cache.tiers, interpret=ops._interpret())
            return out.reshape(b, sq, h, dh)
        k, v = cache.read(q.dtype)
        sk = k.shape[1]
        kvh = k.shape[2]
        g = h // kvh
        scale = 1.0 / math.sqrt(dh)
        qg = q.reshape(b, sq, kvh, g, dh)
        # Match the cache's head_dim TP sharding: the contraction then runs as
        # sharded partial sums + a 33MB score psum instead of all-gathering the
        # multi-GB K (§Perf decode iteration).
        qg = shard(qg, "batch", None, None, None, "model")
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                       preferred_element_type=jnp.float32) * scale
        pos = jnp.arange(sk)
        # Per-slot length mask: slot b attends only its own filled positions.
        valid = pos[None, :] < cache.length[:, None]            # [B, Smax]
        s = jnp.where(valid[:, None, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(q.dtype), v,
                         preferred_element_type=jnp.float32)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dh)
        return out.astype(q.dtype)


# --------------------------------------------------------------- GQA attention
def attention_init(key, cfg, dtype=jnp.bfloat16):
    keys = jax.random.split(key, 4)
    d, h, kvh, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "q_proj": dense_init(keys[0], d, h * dh, dtype),
        "k_proj": dense_init(keys[1], d, kvh * dh, dtype),
        "v_proj": dense_init(keys[2], d, kvh * dh, dtype),
        "o_proj": dense_init(keys[3], h * dh, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"g": jnp.ones((dh,), dtype)}
        p["k_norm"] = {"g": jnp.ones((dh,), dtype)}
    return p


def attention_apply(params, x, rt: Runtime, cfg, name: str, *,
                    positions=None, cache: Optional[KVCache] = None,
                    cache_start=None, seq_lengths=None, active=None,
                    verify_window: bool = False):
    """GQA attention with RoPE (+ optional qk_norm).  If `cache` is given,
    runs in incremental mode: S > 1 prefills the cache from position 0
    (right-padded prompts supported via ``seq_lengths`` [B], the true token
    counts); S == 1 appends one token at each slot's own fill point, with
    ``active`` [B] masking writes for finished/empty slots.

    NOTE: unlike the scalar-length seed, a multi-token call on a warm cache
    does NOT append at the fill point (per-slot lengths have no single
    append position).  Chunked prefill must pass ``cache_start`` (and gets
    the uniform-start semantics); otherwise S > 1 means prefill-from-
    scratch — EXCEPT under ``verify_window``, the speculative verify
    path: S > 1 tokens append at each slot's own fill point, with the
    q/k/v/o projections batched over the window (per-row quantization +
    exact integer accumulation make them bit-identical to S separate
    decode projections) and the attention core replaying ``append`` +
    ``decode_attention`` per position, so position j's output — and its
    KV write — is bit-identical to the j-th sequential decode step
    (flash_attention's blocked online softmax would NOT be: it
    reassociates the reduction).  Returns (out, new_cache)."""
    b, s, d = x.shape
    h, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if rt.tp is not None:
        # Inside shard_map the q (and, when the KV heads divide, k/v)
        # projections are head-sharded: local head counts drive every
        # reshape, and GQA grouping is re-derived from the LOCAL ratio —
        # exact because a contiguous query-head slice maps onto the
        # matching KV-head slice (kv_shards) or onto the one replicated
        # MQA head (num_kv_heads == 1 fallback).
        h //= rt.tp.n
        if rt.tp.kv_shards:
            kvh //= rt.tp.n
    if positions is None:
        if cache_start is not None:
            base = jnp.asarray(cache_start, jnp.int32).reshape(-1, 1)
        elif cache is not None and (s == 1 or verify_window):
            base = cache.length[:, None]   # append at each slot's fill point
        else:
            base = jnp.zeros((1, 1), jnp.int32)    # prefill from scratch
        positions = base + jnp.arange(s)[None, :].astype(jnp.int32)
        positions = jnp.broadcast_to(positions, (b, s))

    # q/k/v read the same x: share one activation quantization (exact).
    acts: Dict[Any, Any] = {}
    q = linear(params["q_proj"], x, rt, f"{name}.q_proj",
               act_quants=acts).reshape(b, s, h, dh)
    k = linear(params["k_proj"], x, rt, f"{name}.k_proj",
               act_quants=acts).reshape(b, s, kvh, dh)
    v = linear(params["v_proj"], x, rt, f"{name}.v_proj",
               act_quants=acts).reshape(b, s, kvh, dh)
    if cfg.qk_norm:
        q = qk_headnorm(params["q_norm"], q)
        k = qk_headnorm(params["k_norm"], k)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", None, "model", None)
    k = shard(k, "batch", None, "model", None)

    new_cache = None
    if cache is not None:
        if s == 1:
            new_cache = cache.append(k, v, active=active)
            out = decode_attention(q, new_cache)
        elif verify_window:
            # Speculative verify: per-position append + decode_attention
            # replay (see docstring — the batched work happened in the
            # projections; the core stays sequential for bit-identity).
            qs = jnp.swapaxes(q, 0, 1)[:, :, None]     # [S, B, 1, H, dh]
            ks = jnp.swapaxes(k, 0, 1)[:, :, None]
            vs = jnp.swapaxes(v, 0, 1)[:, :, None]

            def vstep(c, xs):
                q_t, k_t, v_t = xs
                c2 = c.append(k_t, v_t, active=active)
                return c2, decode_attention(q_t, c2)

            new_cache, outs = jax.lax.scan(vstep, cache, (qs, ks, vs))
            out = jnp.swapaxes(outs[:, :, 0], 0, 1)    # [B, S, H, dh]
        else:
            start = 0 if cache_start is None else cache_start
            new_cache = cache.update(k, v, start, new_length=seq_lengths)
            kf, vf = new_cache.read(q.dtype)
            # q_offset = start: with right-padding, pad queries past a slot's
            # true length attend only already-written positions (causal) and
            # their outputs are discarded by the caller's length gather.
            out = flash_attention(q, kf, vf, causal=True, q_offset=start)
    else:
        out = flash_attention(q, k, v, causal=True)
    out = out.reshape(b, s, h * dh)
    return linear(params["o_proj"], out, rt, f"{name}.o_proj"), new_cache


# ----------------------------------------------------------------- SwiGLU MLP
def mlp_init(key, d_model: int, d_ff: int, dtype=jnp.bfloat16):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate_proj": dense_init(k1, d_model, d_ff, dtype),
        "up_proj": dense_init(k2, d_model, d_ff, dtype),
        "down_proj": dense_init(k3, d_ff, d_model, dtype),
    }


def mlp_apply(params, x, rt: Runtime, name: str):
    # gate/up read the same x: share one activation quantization (exact).
    acts: Dict[Any, Any] = {}
    gate = linear(params["gate_proj"], x, rt, f"{name}.gate_proj",
                  act_quants=acts)
    up = linear(params["up_proj"], x, rt, f"{name}.up_proj", act_quants=acts)
    hidden = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    hidden = shard(hidden, "batch", None, "model")
    return linear(params["down_proj"], hidden, rt, f"{name}.down_proj")
