"""Pallas TPU kernel: single-token decode attention over the KV cache AS
STORED — the decode-memory half of the paper's precision scaling, read at
each slot's own precision.

The cache (``models.layers.KVCache``) holds K and V in one of four
encodings: dense bf16, homogeneous int8 codes, homogeneous int4
nibble-packed codes, or the mixed per-slot byte-lane arena (bf16 byte
pairs, int8 codes and int4 nibbles side by side, one tier code per slot).
The kernel takes the stored lanes, the bf16 scale rows, the per-slot fill
points ``length`` and tier codes ``kv_bits`` (both as scalar prefetch) and:

  * walks a grid over (slot, position block) whose K/V index maps clamp to
    the slot's last FILLED block, so blocks at or past ``length`` are never
    fetched (the pipeline skips a fetch whose block index repeats) and
    never computed;
  * dequantizes each fetched block in VMEM at the slot's own tier only
    (one ``pl.when`` branch per tier the arena serves), with the cache's
    arithmetic: bf16 lanes reassembled from their byte pairs exactly, int8
    and int4 codes as ``bf16(code * scale)``;
  * runs the attention of ``layers.decode_attention`` per KV head for its
    group of query heads: bf16 ``q . k`` into f32, times 1/sqrt(dh),
    positions at or past ``length`` masked, a whole-row f32 softmax over
    the filled blocks, ``p`` cast to bf16, ``p . v`` into f32.

Every encoding dequantizes to the same bf16 values in the same VMEM
layout and then shares one attention body, so a mixed-arena slot at tier
m computes bit for bit what the homogeneous cache at m computes — the
contract ``KVCache.read``'s optimization barrier keeps on the jnp path.

Lane layouts are whole in every block: a u8 block is [positions, KV heads,
lanes] exactly as the arena stores it (a reshape or transpose of the arena
would cost an arena-sized copy).  Byte pairs and nibbles are moved into
element order by exact 0/1 selection matmuls on the MXU (each output takes
one integer < 256 times 1), because Mosaic has no lane gather; shifts run
on int32 (Mosaic refuses shifts of sub-32-bit vectors).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Positions per grid step: a u8 block is [BLOCK, KV heads, lanes], 256 KiB
# at 8 heads x 256 lanes.  Smaller blocks skip more of a half-filled slot
# and compile faster (Mosaic unrolls every head of a block; compiled for a
# v5e, the (16, 4) arena's kernel takes 6.5 s at 128 positions, 13.5 s at
# 256, 38 s at 512); larger ones take fewer grid steps.
BLOCK: int = 128
# The per-slot scratch (scores and dequantized V of every filled block)
# grows with max_len: granite's 2560 positions x 8 heads x 128 lanes of V
# is 5 MiB.  Blocks, selection matrices and temporaries take up to about
# WORKING_VMEM more.  The kernel asks for more than the default scoped
# VMEM only where that does not fit (max_len 8192 needs 26 MiB), and
# never for more than MAX_VMEM of the v5e's 128 MiB: a cache that would
# need more takes the jnp path (:func:`tiles_on_tpu`).
DEFAULT_VMEM: int = 16 << 20
WORKING_VMEM: int = 8 << 20
MAX_VMEM: int = 96 << 20
NEG: float = -1e30


def _vmem_need(max_len: int, heads: int, kv_heads: int,
               head_dim: int) -> int:
    """Bytes of VMEM the kernel uses: its scratch plus the working set."""
    scratch = (4 * max_len * heads
               + 2 * (max_len + BLOCK) * kv_heads * head_dim)
    return scratch + WORKING_VMEM


def tiles_on_tpu(max_len: int, heads: int, kv_heads: int,
                 head_dim: int) -> bool:
    """Whether the compiled kernel tiles these cache shapes (the local
    head counts under tensor parallelism): head_dim whole lane tiles (so
    int8 lanes slice at a tile edge), whole blocks of positions (the scale
    rows' lane axis, 128 lanes a tile), and a scratch within
    :data:`MAX_VMEM`."""
    return (head_dim % 128 == 0 and max_len % BLOCK == 0
            and _vmem_need(max_len, heads, kv_heads, head_dim) <= MAX_VMEM)


def positions_fetched(lengths: Any) -> Any:
    """Positions the kernel fetches per slot per layer for fill points
    ``lengths``: whole blocks up to the last filled one, and at least one
    block (an empty slot still reads block 0)."""
    blocks = np.maximum(-(-np.asarray(lengths) // BLOCK), 1)
    return blocks * BLOCK


def vmem_limit(max_len: int, heads: int, kv_heads: int,
               head_dim: int) -> Optional[int]:
    """Scoped VMEM the kernel asks for: None (the default) unless its
    scratch plus working set exceeds :data:`DEFAULT_VMEM`."""
    need = _vmem_need(max_len, heads, kv_heads, head_dim)
    return None if need <= DEFAULT_VMEM else -(-need // (1 << 20)) << 20


def fetch_block(length: Any, j: Any) -> Any:
    """The position block grid step ``j`` fetches for a slot filled to
    ``length``: ``j`` itself up to the slot's last filled block, that block
    after it (a repeated block index is not fetched again).  An empty slot
    fetches block 0."""
    return jnp.minimum(j, jnp.maximum((length + BLOCK - 1) // BLOCK - 1, 0))


def _nibble_lanes(lanes: int, head_dim: int) -> int:
    """Leading lanes of a block that hold int4 nibbles, rounded up to whole
    128-lane tiles (the rest of a mixed arena's lanes is padding)."""
    return min(lanes, -(-(head_dim // 2) // 128) * 128)


def selection_matrices(lanes: int, head_dim: int,
                       tiers: Sequence[int]) -> Tuple[jax.Array, ...]:
    """0/1 bf16 matrices that move stored u8 lanes into element order:

      * tier 16, [2*dh, 2*dh]: byte 2i -> column i (low bytes), byte 2i+1
        -> column dh+i (high bytes);
      * tier 4, [2, nibble lanes, dh]: the low nibble of byte j ->
        element 2j, the high nibble -> element 2j+1.
    """
    out = []
    if 16 in tiers:
        p = np.zeros((2 * head_dim, 2 * head_dim), np.float32)
        i = np.arange(head_dim)
        p[2 * i, i] = 1
        p[2 * i + 1, head_dim + i] = 1
        out.append(p)
    if 4 in tiers:
        e = np.zeros((2, _nibble_lanes(lanes, head_dim), head_dim),
                     np.float32)
        j = np.arange(head_dim // 2)
        e[0, j, 2 * j] = 1
        e[1, j, 2 * j + 1] = 1
        out.append(e)
    return tuple(jnp.asarray(m, jnp.bfloat16) for m in out)


def _exact_dot(x: jax.Array, sel: jax.Array) -> jax.Array:
    """Integer-valued bf16 [r, lanes] times a 0/1 selection matrix: every
    output is one stored integer times 1, exact in f32."""
    return jax.lax.dot_general(x, sel, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dequant(ref: Any, h: int, tier: int, scale: Optional[jax.Array],
             sels: Dict[int, jax.Array], head_dim: int,
             dtype: Any) -> jax.Array:
    """KV head ``h`` of a block as stored -> [positions, head_dim] in
    ``dtype``.

    ``ref``: the block [positions, KVH, lanes] (bf16 values, int8 codes or
    u8 bytes); ``scale``: its f32 [positions, 1] scale column (quantized
    tiers).  Only the lanes the tier uses are read."""
    if ref.dtype == jnp.bfloat16:                 # dense bf16 cache
        return ref[:, h, :].astype(dtype)
    if tier == 16:                                # bf16 from byte pairs
        x = ref[:, h, :2 * head_dim].astype(jnp.int32)
        r = _exact_dot(x.astype(jnp.bfloat16), sels[16]).astype(jnp.int32)
        bits = ((r[:, head_dim:] << 8) | r[:, :head_dim]) << 16
        val = jax.lax.bitcast_convert_type(bits, jnp.float32)
        return val.astype(jnp.bfloat16).astype(dtype)
    if tier == 8:
        code = ref[:, h, :head_dim].astype(jnp.int32)
        if ref.dtype == jnp.uint8:
            code = jnp.where(code >= 128, code - 256, code)
        code = code.astype(jnp.float32)
    else:                                         # int4 nibbles
        sel = sels[4]
        x = ref[:, h, :sel.shape[1]].astype(jnp.int32)
        lo = x & 0xF
        hi = (x >> 4) & 0xF
        lo = jnp.where(lo >= 8, lo - 16, lo).astype(jnp.bfloat16)
        hi = jnp.where(hi >= 8, hi - 16, hi).astype(jnp.bfloat16)
        code = _exact_dot(lo, sel[0]) + _exact_dot(hi, sel[1])
    # bf16(code) * bf16(scale), rounded once to the cache dtype: the f32
    # product of two 8-bit significands is exact.
    return (code * scale).astype(dtype)


def _kernel(len_ref: Any, bits_ref: Any, q_ref: Any, k_ref: Any, v_ref: Any,
            *rest: Any, tiers: Tuple[int, ...], quantized: bool,
            n_sel: int, bs: int, kvh: int, group: int,
            head_dim: int) -> None:
    ks_ref = vs_ref = None
    if quantized:
        ks_ref, vs_ref, *rest = rest
    sel_refs, (o_ref, s_scr, k_scr, v_scr) = rest[:n_sel], rest[n_sel:]
    b, j = pl.program_id(0), pl.program_id(1)
    n = len_ref[b]
    nblk = jnp.maximum((n + bs - 1) // bs, 1)
    dtype = q_ref.dtype
    h_all = q_ref.shape[0]

    @pl.when(j < nblk)
    def _fill() -> None:
        sels: Dict[int, jax.Array] = {}
        if n_sel:
            order = [t for t in (16, 4) if t in tiers]
            sels = {t: r[...] for t, r in zip(order, sel_refs)}
        kst = vst = None
        if quantized:
            kst = ks_ref[...].astype(jnp.float32).T          # [bs, kvh]
            vst = vs_ref[...].astype(jnp.float32).T
        row = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)

        def fill(tier: int) -> None:
            for h in range(kvh):
                kc = vc = None
                if quantized:
                    kc, vc = kst[:, h:h + 1], vst[:, h:h + 1]
                k_scr[h] = _dequant(k_ref, h, tier, kc, sels, head_dim,
                                    dtype)
                vh = _dequant(v_ref, h, tier, vc, sels, head_dim, dtype)
                # Rows past the fill point may hold anything (a NaN byte
                # pair): p is 0 there, and 0 * NaN is not.
                v_scr[j, h] = jnp.where(row < n, vh, jnp.zeros_like(vh))

        # As KVCache's candidate select: a code outside the arena's modes
        # (a zeroed arena's free slot) reads at the last mode.
        bits = bits_ref[b]
        other = jnp.bool_(True)
        for t in tiers[:-1]:
            pl.when(bits == t)(functools.partial(fill, t))
            other = other & (bits != t)
        pl.when(other)(functools.partial(fill, tiers[-1]))

        q = q_ref[...]
        grp = jax.lax.broadcasted_iota(jnp.int32, (h_all, bs), 0) // group
        s = jnp.zeros((h_all, bs), jnp.float32)
        for h in range(kvh):
            sh = jax.lax.dot_general(q, k_scr[h], (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            s = jnp.where(grp == h, sh, s)
        s = s * (1.0 / math.sqrt(head_dim))
        col = j * bs + jax.lax.broadcasted_iota(jnp.int32, (h_all, bs), 1)
        s_scr[j] = jnp.where(col < n, s, NEG)

    @pl.when(j == nblk - 1)
    def _finish() -> None:
        # jax.nn.softmax over the filled blocks, then p . v.
        m = jax.lax.fori_loop(
            0, nblk, lambda i, m: jnp.maximum(
                m, jnp.max(s_scr[i], axis=1, keepdims=True)),
            jnp.full((h_all, 1), -jnp.inf, jnp.float32))

        def expsum(i: Any, tot: jax.Array) -> jax.Array:
            e = jnp.exp(s_scr[i] - m)
            s_scr[i] = e
            return tot + jnp.sum(e, axis=1, keepdims=True)

        tot = jax.lax.fori_loop(0, nblk, expsum,
                                jnp.zeros((h_all, 1), jnp.float32))
        grp = jax.lax.broadcasted_iota(jnp.int32, (h_all, head_dim),
                                       0) // group

        def pv(i: Any, acc: jax.Array) -> jax.Array:
            p = (s_scr[i] / tot).astype(dtype)
            blk = jnp.zeros_like(acc)
            for h in range(kvh):
                oh = jax.lax.dot_general(p, v_scr[i, h],
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                blk = jnp.where(grp == h, oh, blk)
            return acc + blk

        acc = jax.lax.fori_loop(0, nblk, pv,
                                jnp.zeros((h_all, head_dim), jnp.float32))
        o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tiers", "interpret"))
def kv_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        k_scale: Optional[jax.Array],
                        v_scale: Optional[jax.Array], length: jax.Array,
                        kv_bits: Optional[jax.Array], *,
                        tiers: Tuple[int, ...],
                        interpret: bool = False) -> jax.Array:
    """Decode attention of ``q`` [B, H, dh] against a stored KV cache.

    ``k``/``v``: [B, S, KVH, lanes] as stored — bf16 values, int8 codes,
    or u8 lanes (int4 nibbles; the mixed arena's byte lanes).
    ``k_scale``/``v_scale``: bf16 [B, S, KVH, 1] for quantized storage,
    else None.  ``length``: int32 [B] fill points.  ``kv_bits``: int32 [B]
    per-slot tier codes of the mixed arena, else None.  ``tiers``: the tier
    codes the stored lanes serve (``KVCache.modes``; one code for a
    homogeneous cache).  Returns [B, H, dh] in q.dtype; H must be a
    multiple of KVH (query heads grouped per KV head, head-major)."""
    b, h_all, dh = q.shape
    _, s_len, kvh, lanes = k.shape
    assert h_all % kvh == 0, (h_all, kvh)
    assert s_len % BLOCK == 0, (s_len, BLOCK)
    bs, nj = BLOCK, s_len // BLOCK
    quantized = k_scale is not None
    if kv_bits is None:
        assert len(tiers) == 1, tiers
        kv_bits = jnp.full((b,), tiers[0], jnp.int32)
    sels = selection_matrices(lanes, dh, tiers) \
        if k.dtype == jnp.uint8 else ()

    def kv_map(i: Any, j: Any, len_ref: Any, bits_ref: Any) -> Any:
        return (i, fetch_block(len_ref[i], j), 0, 0)

    def scale_map(i: Any, j: Any, len_ref: Any, bits_ref: Any) -> Any:
        return (i, 0, fetch_block(len_ref[i], j))

    def slot_map(i: Any, j: Any, len_ref: Any, bits_ref: Any) -> Any:
        return (i, 0, 0)

    in_specs = [pl.BlockSpec((None, h_all, dh), slot_map),
                pl.BlockSpec((None, bs, kvh, lanes), kv_map),
                pl.BlockSpec((None, bs, kvh, lanes), kv_map)]
    args = [q, k, v]
    if quantized:
        # The scale rows' stored layout keeps positions minor (XLA lays a
        # trailing unit dim out major), so [B, KVH, S] is a free view.
        in_specs += [pl.BlockSpec((None, kvh, bs), scale_map)] * 2
        args += [jnp.transpose(k_scale[..., 0], (0, 2, 1)),
                 jnp.transpose(v_scale[..., 0], (0, 2, 1))]
    for m in sels:
        in_specs.append(pl.BlockSpec(
            m.shape, lambda i, j, len_ref, bits_ref, nd=m.ndim: (0,) * nd))
        args.append(m)

    kernel = functools.partial(
        _kernel, tiers=tuple(tiers), quantized=quantized, n_sel=len(sels),
        bs=bs, kvh=kvh, group=h_all // kvh, head_dim=dh)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, nj), in_specs=in_specs,
            out_specs=pl.BlockSpec((None, h_all, dh), slot_map),
            scratch_shapes=[pltpu.VMEM((nj, h_all, bs), jnp.float32),
                            pltpu.VMEM((kvh, bs, dh), q.dtype),
                            pltpu.VMEM((nj, kvh, bs, dh), q.dtype)]),
        out_shape=jax.ShapeDtypeStruct((b, h_all, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(s_len, h_all, kvh, dh)),
        interpret=interpret,
    )(length.astype(jnp.int32), kv_bits.astype(jnp.int32), *args)
