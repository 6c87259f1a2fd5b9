"""Plain reference of a dense GQA decoder with a SwiGLU MLP, served at
per-request precision tiers.  It imports nothing of the program under test.

What it computes, for one sequence at one tier (``w_bits``, ``a_bits``,
``kv_bits``), layer by layer in float32 (``highest`` matmul precision):

* every float the configuration stores in ``dtype`` (bfloat16) is rounded to
  it where it is stored: the embedding, each norm (its inverse RMS and both
  products), each projection's output, RoPE, the softmax weights of a
  decoded row, each attention output, the residual stream, the SwiGLU
  product and the logits;
* a projection ``y = x @ W`` is served as integers: ``W`` is quantized once
  at 8 bits per output channel (scale ``max|W| / 127``, round to nearest),
  then its ``8 - w_bits`` low bits are dropped (an arithmetic shift; the
  scale grows by the same power of two); ``x`` is quantized per row at
  ``a_bits`` (scale ``max|x| * (1 / (2**(a_bits-1) - 1))``, round to
  nearest); the integer product is exact and scaled back by both scales;
* keys and values are stored per (position, head) at ``kv_bits``: 16 keeps
  the bfloat16 value; 8 and 4 quantize symmetrically with a bfloat16 scale
  ``max|x| / (2**(bits-1) - 1)``; every query reads the stored keys and
  values, its own position included;
* attention is causal softmax over the stored keys with scale
  ``1/sqrt(head_dim)``; RoPE is the split-half form; with ``qk_norm`` each
  head of q and k is RMS-normalised before RoPE.

``mode="control"`` is the same computation one precision step lower: every
value stored in bfloat16 above is stored in float8_e4m3fn instead.

The sequences of one call are packed into one padded row of tokens, so the
whole sample runs through one compiled layer program.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights

Q_BLOCK = 512          # query rows per attention block
PAD_TO = 1024          # packed length is a multiple of this (few programs)
FP8_MAX = 448.0


def _store(x: jax.Array, mode: str) -> jax.Array:
    """Round to the precision a stored float has (bfloat16, or float8 in
    the control), returned as float32."""
    if mode == "control":
        return jnp.clip(x, -FP8_MAX, FP8_MAX).astype(
            jnp.float8_e4m3fn).astype(jnp.float32)
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _rmsnorm(x: jax.Array, g: jax.Array, eps: float, mode: str) -> jax.Array:
    """RMSNorm of a stored activation: the inverse RMS is stored, and each
    of the two products is stored (the configuration's elementwise ops run
    in its dtype)."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = _store(jax.lax.rsqrt(var + eps), mode)
    return _store(_store(x * inv, mode) * g.astype(jnp.float32), mode)


def _headnorm(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _quant_weight(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """8-bit per-output-channel codes (int32) and their f32 scale [1, N]."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=0, keepdims=True)
    s8 = jnp.maximum(amax, 1e-8) * (jnp.float32(1.0) / jnp.float32(127.0))
    q8 = jnp.clip(jnp.round(wf / s8), -128, 127).astype(jnp.int32)
    return q8, s8


def _qlinear(x: jax.Array, q8: jax.Array, s8: jax.Array, w_bits: int,
             a_bits: int) -> jax.Array:
    shift = 8 - w_bits
    qw = (q8 >> shift).astype(jnp.int8)
    sw = s8 * jnp.float32(2.0 ** shift)
    qmax = float((1 << (a_bits - 1)) - 1)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    sa = jnp.maximum(amax, 1e-8) * (jnp.float32(1.0) / jnp.float32(qmax))
    qa = jnp.clip(jnp.round(x / sa), -qmax - 1.0, qmax).astype(jnp.int8)
    acc = jnp.matmul(qa, qw, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sa * sw


def _kv_store(x: jax.Array, bits: int, mode: str) -> jax.Array:
    """[P, KVH, Dh] keys or values as stored at ``bits`` and read back."""
    if bits == 16:
        return _store(x, mode)
    qmax = (1 << (bits - 1)) - 1
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax)
    sb = scale.astype(jnp.bfloat16).astype(jnp.float32)
    return _store(q * sb, mode)


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs            # [P, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q: jax.Array, k: jax.Array, v: jax.Array, seg: jax.Array,
               pos: jax.Array, dec: jax.Array, mode: str) -> jax.Array:
    """q [P, H, Dh]; k, v [P, KVH, Dh]; causal within each segment.  Rows
    decoded one token at a time (``dec``) store their softmax weights in the
    configuration's dtype before weighting the values, as a decode step
    does."""
    p_len, h, dh = q.shape
    g = h // k.shape[1]
    kr = jnp.repeat(k, g, axis=1)
    vr = jnp.repeat(v, g, axis=1)
    scale = 1.0 / math.sqrt(dh)

    def block(i: jax.Array) -> jax.Array:
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        sb = jax.lax.dynamic_slice_in_dim(seg, i * Q_BLOCK, Q_BLOCK, 0)
        pb = jax.lax.dynamic_slice_in_dim(pos, i * Q_BLOCK, Q_BLOCK, 0)
        db = jax.lax.dynamic_slice_in_dim(dec, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, kr) * scale
        ok = (sb[:, None] == seg[None, :]) & (pos[None, :] <= pb[:, None])
        s = jnp.where(ok[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(db[None, :, None], _store(p, mode), p)
        return jnp.einsum("hqk,khd->qhd", p, vr)

    out = jax.lax.map(block, jnp.arange(p_len // Q_BLOCK))
    return out.reshape(p_len, h, dh)


@functools.partial(jax.jit, static_argnames=("cfg_items", "tiers", "mode"))
def _layer(x: jax.Array, lp: Dict[str, jax.Array], seg: jax.Array,
           pos: jax.Array, dec: jax.Array, row_tier: jax.Array, *,
           cfg_items: Tuple,
           tiers: Tuple[Tuple[int, int, int], ...], mode: str) -> jax.Array:
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]
    h, kvh, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    p_len = x.shape[0]

    def by_tier(fn: Any) -> jax.Array:
        outs = [fn(t) for t in tiers]
        y = outs[-1]
        for i in range(len(tiers) - 1):
            sel = (row_tier == i).reshape((-1,) + (1,) * (y.ndim - 1))
            y = jnp.where(sel, outs[i], y)
        return y

    def proj(inp: jax.Array, name: str) -> jax.Array:
        q8, s8 = _quant_weight(lp[name])
        return by_tier(lambda t: _store(_qlinear(inp, q8, s8, t[0], t[1]),
                                        mode))

    hx = _rmsnorm(x, lp["mixer_norm/g"], eps, mode)
    q = proj(hx, "attn/q_proj/w").reshape(p_len, h, dh)
    k = proj(hx, "attn/k_proj/w").reshape(p_len, kvh, dh)
    v = proj(hx, "attn/v_proj/w").reshape(p_len, kvh, dh)
    if cfg.get("qk_norm"):
        q = _store(_headnorm(q, lp["attn/q_norm/g"], eps), mode)
        k = _store(_headnorm(k, lp["attn/k_norm/g"], eps), mode)
    q = _store(_rope(q, pos, cfg["rope_theta"]), mode)
    k = _store(_rope(k, pos, cfg["rope_theta"]), mode)
    ks = by_tier(lambda t: _kv_store(k, t[2], mode))
    vs = by_tier(lambda t: _kv_store(v, t[2], mode))
    att = _store(_attention(q, ks, vs, seg, pos, dec, mode),
                 mode).reshape(p_len, h * dh)
    x = _store(x + proj(att, "attn/o_proj/w"), mode)
    h2 = _rmsnorm(x, lp["ff_norm/g"], eps, mode)
    gate = proj(h2, "mlp/gate_proj/w")
    up = proj(h2, "mlp/up_proj/w")
    hidden = _store(_store(jax.nn.silu(gate), mode) * up, mode)
    return _store(x + proj(hidden, "mlp/down_proj/w"), mode)


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def _layer_params(base: jax.Array, layer: jax.Array, *,
                  shapes: Tuple[Tuple[str, Tuple[int, ...]], ...],
                  dtype: str) -> Dict[str, jax.Array]:
    return {name: weights.layer_leaf(base, "periods/pos0/" + name, shape,
                                     jnp.dtype(dtype), layer)
            for name, shape in shapes}


@functools.partial(jax.jit, static_argnames=("cfg_items", "tiers", "mode",
                                             "tied"))
def _head(x: jax.Array, rows: jax.Array, row_tier: jax.Array,
          final_g: jax.Array, head_w: jax.Array, *, cfg_items: Tuple,
          tiers: Tuple[Tuple[int, int, int], ...], mode: str,
          tied: bool) -> jax.Array:
    cfg = dict(cfg_items)
    hx = _rmsnorm(jnp.take(x, rows, axis=0), final_g, cfg["rms_norm_eps"],
                  mode)
    if tied:
        return _store(hx @ head_w.astype(jnp.float32).T, mode)
    q8, s8 = _quant_weight(head_w)
    tier_rows = jnp.take(row_tier, rows, axis=0)
    outs = [_store(_qlinear(hx, q8, s8, t[0], t[1]), mode) for t in tiers]
    y = outs[-1]
    for i in range(len(tiers) - 1):
        y = jnp.where((tier_rows == i)[:, None], outs[i], y)
    return y


def pack(seqs: Sequence[Dict[str, Any]], tier_names: Sequence[str]
         ) -> Dict[str, np.ndarray]:
    """Pack sequences (``tokens``, ``tier``, ``score_from``) into one padded
    row: tokens, segment ids (-1 = pad), positions, tier index per row,
    whether a row was decoded (past ``score_from``, the prompt's last row),
    and the packed indices of the scored rows (``score_from`` to the
    end)."""
    total = sum(len(s["tokens"]) for s in seqs)
    p_len = max(PAD_TO, -(-total // PAD_TO) * PAD_TO)
    tok = np.zeros(p_len, np.int32)
    seg = np.full(p_len, -1, np.int32)
    pos = np.zeros(p_len, np.int32)
    tier = np.zeros(p_len, np.int32)
    dec = np.zeros(p_len, bool)
    rows: List[int] = []
    off = 0
    for i, s in enumerate(seqs):
        n = len(s["tokens"])
        tok[off:off + n] = s["tokens"]
        seg[off:off + n] = i
        pos[off:off + n] = np.arange(n)
        tier[off:off + n] = list(tier_names).index(s["tier"])
        dec[off + s["score_from"] + 1:off + n] = True
        rows.extend(range(off + s["score_from"], off + n))
        off += n
    return {"tokens": tok, "seg": seg, "pos": pos, "tier": tier, "dec": dec,
            "rows": np.asarray(rows, np.int32)}


def as_run(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration as it is served: its published values with its
    ``departures["run"]`` laid over them.  This reference has no muP
    multipliers, so it refuses a configuration served with any."""
    run = {**cfg, **cfg.get("departures", {}).get("run", {})}
    neutral = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
               "logits_scaling": 1.0,
               "attention_multiplier": 1.0 / math.sqrt(run["head_dim"])}
    for key, value in neutral.items():
        if key in run and not math.isclose(run[key], value):
            raise ValueError(f"{key}={run[key]}: the reference runs only "
                             f"{value}")
    return run


def logits(cfg: Dict[str, Any], seed: int,
           seqs: Sequence[Dict[str, Any]], mode: str = "stated"
           ) -> jax.Array:
    """f32 logits [n_scored, padded_vocab] of every scored row of ``seqs``
    (each ``{"tokens", "tier", "score_from"}``), in packing order, for the
    configuration as it is served (``as_run``)."""
    cfg = as_run(cfg)
    tier_names = list(cfg["tiers"])
    tiers = tuple((t["w_bits"], t["a_bits"], t["kv_bits"])
                  for t in cfg["tiers"].values())
    packed = pack(seqs, tier_names)
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "rms_norm_eps", "rope_theta",
            "qk_norm")
    cfg_items = tuple((k, cfg.get(k)) for k in keys)
    dtype = cfg["dtype"]
    d, vp = cfg["hidden_size"], cfg["padded_vocab"]
    with jax.default_matmul_precision("highest"):
        base = weights.base_key(seed)
        emb = jax.jit(lambda k: weights.leaf_value(
            k, "embed/emb", (vp, d), jnp.dtype(dtype)))(
                weights.leaf_key(base, "embed/emb"))
        x = _store(jnp.take(emb, jnp.asarray(packed["tokens"]), axis=0)
                   .astype(jnp.float32), mode)
        seg, pos = jnp.asarray(packed["seg"]), jnp.asarray(packed["pos"])
        dec = jnp.asarray(packed["dec"])
        row_tier = jnp.asarray(packed["tier"])
        shapes = tuple(sorted(weights.layer_shapes(cfg).items()))
        for layer in range(cfg["num_hidden_layers"]):
            lp = _layer_params(base, jnp.int32(layer), shapes=shapes,
                               dtype=dtype)
            x = _layer(x, lp, seg, pos, dec, row_tier, cfg_items=cfg_items,
                       tiers=tiers, mode=mode)
            del lp
        tied = bool(cfg["tie_word_embeddings"])
        if tied:
            head_w = emb
        else:
            del emb
            head_w = jax.jit(lambda k: weights.leaf_value(
                k, "lm_head/w", (d, vp), jnp.dtype(dtype)))(
                    weights.leaf_key(base, "lm_head/w"))
        final_g = jnp.ones((d,), jnp.dtype(dtype))
        return _head(x, jnp.asarray(packed["rows"]), row_tier, final_g,
                     head_w, cfg_items=cfg_items, tiers=tiers, mode=mode,
                     tied=tied)
