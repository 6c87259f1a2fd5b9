"""Decoder-only LM stack covering all assigned families: dense, MoE, hybrid
(Mamba+attention interleave), pure SSM, and stub-fronted VLM/audio backbones.

The stack scans over *periods* (cfg.period_pattern()) with stacked params, so
a 72-layer hybrid compiles as a 9-step scan over a static 8-layer body —
small HLO, layer-granular remat, and per-period stacked KV/SSM caches.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models import layers, moe, ssm
from repro.models.config import ArchConfig


class LM:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.pattern = cfg.period_pattern()

    # ------------------------------------------------------------------ init
    def _period_init(self, key):
        cfg = self.cfg
        p: Dict[str, Any] = {}
        ks = jax.random.split(key, 4 * len(self.pattern))
        for i, (mixer, ff) in enumerate(self.pattern):
            blk: Dict[str, Any] = {"mixer_norm": layers.rmsnorm_init(cfg.d_model, cfg.dtype)}
            if mixer == "attn":
                blk["attn"] = layers.attention_init(ks[4 * i], cfg, cfg.dtype)
            else:
                blk["mamba"] = ssm.ssm_init(ks[4 * i], cfg, cfg.dtype)
            if ff is not None:
                blk["ff_norm"] = layers.rmsnorm_init(cfg.d_model, cfg.dtype)
                if ff == "mlp":
                    blk["mlp"] = layers.mlp_init(ks[4 * i + 1], cfg.d_model,
                                                 cfg.d_ff, cfg.dtype)
                else:
                    blk["moe"] = moe.moe_init(ks[4 * i + 1], cfg, cfg.dtype)
            p[f"pos{i}"] = blk
        return p

    def init(self, key):
        cfg = self.cfg
        k_emb, k_per, k_head = jax.random.split(key, 3)
        period_keys = jax.random.split(k_per, cfg.n_periods)
        periods = jax.vmap(self._period_init)(period_keys)
        params = {
            "embed": {"emb": (jax.random.normal(
                k_emb, (cfg.padded_vocab, cfg.d_model), jnp.float32) * 0.02
            ).astype(cfg.dtype)},
            "periods": periods,
            "final_norm": layers.rmsnorm_init(cfg.d_model, cfg.dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = layers.dense_init(
                k_head, cfg.d_model, cfg.padded_vocab, cfg.dtype)
        return params

    # ------------------------------------------------------------- internals
    def _embed(self, params, tokens=None, embeds=None):
        if embeds is not None:
            return embeds.astype(self.cfg.dtype)
        return jnp.take(params["embed"]["emb"], tokens, axis=0)

    def _head(self, params, x, rt: layers.Runtime):
        # One named scope for the final norm and either head (the untied
        # head's ``linear`` scope sits inside it).
        with jax.named_scope("lm_head"):
            x = layers.rmsnorm(params["final_norm"], x)
            if self.cfg.tie_embeddings:
                w = params["embed"]["emb"].T
                logits = jnp.matmul(x, w.astype(x.dtype))
            else:
                logits = layers.linear(params["lm_head"], x, rt, "lm_head")
            return shard(logits, "batch", None, "model")

    def _period_body(self, blk_params, x, rt, caches=None, seq_lengths=None,
                     active=None, verify_window=False):
        cfg = self.cfg
        new_caches: Dict[str, Any] = {}
        aux = jnp.zeros((), jnp.float32)
        for i, (mixer, ff) in enumerate(self.pattern):
            blk = blk_params[f"pos{i}"]
            c = None if caches is None else caches.get(f"pos{i}")
            h = layers.rmsnorm(blk["mixer_norm"], x)
            if mixer == "attn":
                out, nc = layers.attention_apply(
                    blk["attn"], h, rt, cfg, f"layers.pos{i}.attn", cache=c,
                    seq_lengths=seq_lengths, active=active,
                    verify_window=verify_window)
            else:
                out, nc = ssm.ssm_apply(
                    blk["mamba"], h, rt, cfg, f"layers.pos{i}.mamba", cache=c,
                    seq_lengths=seq_lengths, active=active,
                    verify_window=verify_window)
            x = x + out
            if caches is not None:
                new_caches[f"pos{i}"] = nc
            if ff is not None:
                h2 = layers.rmsnorm(blk["ff_norm"], x)
                if ff == "mlp":
                    out2 = layers.mlp_apply(blk["mlp"], h2, rt,
                                            f"layers.pos{i}.mlp")
                else:
                    # Verify windows force dropless dispatch: single-token
                    # decode never drops, so position-wise bit-identity
                    # needs every window token admitted too.
                    out2, a = moe.moe_apply(blk["moe"], h2, rt, cfg,
                                            f"layers.pos{i}.moe",
                                            dropless=True if verify_window
                                            else None)
                    aux = aux + a
                x = x + out2
        # Residual stream sharded 2D (batch x d_model): the scan carry is what
        # autodiff saves per period, so sharding d_model over "model" cuts the
        # saved-activation footprint 16x (Megatron-SP-style).
        x = shard(x, "batch", None, "model")
        return x, aux, new_caches

    def _stack(self, params, x, rt, caches=None, seq_lengths=None,
               active=None, verify_window=False):
        if caches is None:
            def body(carry, pp):
                xx, aux = carry
                xx, a, _ = self._period_body(pp, xx, rt)
                return (xx, aux + a), None

            (x, aux), _ = jax.lax.scan(
                jax.checkpoint(body), (x, jnp.zeros((), jnp.float32)),
                params["periods"])
            return x, aux, None

        def body(carry, xs):
            xx, aux = carry
            pp, pc = xs
            xx, a, nc = self._period_body(pp, xx, rt, caches=pc,
                                          seq_lengths=seq_lengths,
                                          active=active,
                                          verify_window=verify_window)
            return (xx, aux + a), nc

        (x, aux), new_caches = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), (params["periods"], caches))
        return x, aux, new_caches

    # ---------------------------------------------------------------- public
    def forward(self, params, rt: layers.Runtime, tokens=None, embeds=None):
        """Full-sequence forward (training / no-cache prefill).
        Returns (logits [B, S, V], aux_loss)."""
        x = self._embed(params, tokens, embeds)
        x = shard(x, "batch", None, None)
        x, aux, _ = self._stack(params, x, rt)
        return self._head(params, x, rt), aux

    def init_cache(self, batch: int, max_len: int, kv_bits=None):
        """Per-period stacked caches for every cache-bearing position.

        ``kv_bits``: None (bf16), 8 (int8), 4 (int4-packed), or a tuple of
        tier codes (e.g. ``(16, 8, 4)``) for the per-slot mixed KV arena —
        see :meth:`repro.models.layers.KVCache.create`."""
        cfg = self.cfg
        single: Dict[str, Any] = {}
        for i, (mixer, _) in enumerate(self.pattern):
            if mixer == "attn":
                single[f"pos{i}"] = layers.KVCache.create(
                    batch, max_len, cfg.num_kv_heads, cfg.head_dim,
                    dtype=cfg.dtype, kv_bits=kv_bits)
            else:
                single[f"pos{i}"] = ssm.SSMCache.create(batch, cfg)
        return jax.tree.map(
            lambda a: jnp.zeros((cfg.n_periods,) + a.shape, a.dtype), single)

    def prefill(self, params, rt, caches, tokens=None, embeds=None,
                seq_lengths=None):
        """Run the prompt through the stack, filling caches from position 0.

        ``seq_lengths`` [B] supports right-padded batches: per-slot cache
        lengths are set to the true token counts, pad positions contribute
        nothing to any cache state, and the returned logits are gathered at
        each row's last REAL position.  Without it, the whole row is real
        and the last position is used (seed behaviour).

        Prefill always (re)fills caches from position 0 — a second prefill
        call on the same caches overwrites them rather than appending
        (chunked prefill is not supported through this entrypoint; see
        ``layers.attention_apply``'s ``cache_start``).
        Returns (last-real-position logits [B, 1, V], new caches)."""
        x = self._embed(params, tokens, embeds)
        x = shard(x, "batch", None, None)
        x, _, new_caches = self._stack(params, x, rt, caches=caches,
                                       seq_lengths=seq_lengths)
        if seq_lengths is None:
            last = x[:, -1:]
        else:
            idx = jnp.clip(seq_lengths.astype(jnp.int32) - 1, 0, x.shape[1] - 1)
            last = jnp.take_along_axis(
                x, idx[:, None, None].astype(jnp.int32), axis=1)
        return self._head(params, last, rt), new_caches

    def decode_step(self, params, rt, caches, tokens=None, embeds=None,
                    active=None):
        """One-token decode against filled caches.  ``active`` [B] masks all
        cache writes (KV append / SSM state) for finished or empty slots so
        a continuous-batching engine can keep them frozen in the batch.
        Returns (logits [B, 1, V], new caches)."""
        x = self._embed(params, tokens, embeds)
        x, _, new_caches = self._stack(params, x, rt, caches=caches,
                                       active=active)
        return self._head(params, x, rt), new_caches

    def verify_step(self, params, rt, caches, tokens, active=None):
        """Multi-token speculative verify: teacher-forced decode of a
        ``[B, W]`` window at each active slot's own fill point.

        ONE batched forward — every projection (and the LM head) runs
        over all W positions at once through the same grouped GEMMs as
        decode — whose position-j output is bit-identical to the j-th of
        W sequential :meth:`decode_step` calls (the attention/SSM cores
        replay the exact decode recurrences internally; see
        ``layers.attention_apply(verify_window=True)`` /
        ``ssm.ssm_apply(verify_window=True)``).  ``active`` [B] masks
        every cache write, so plain slots sharing the batch flow through
        untouched.  KV caches come back appended by W (the engine rolls
        rejected positions back by a length truncation —
        ``slots.truncate_kv_lengths``); SSM caches come back per-step
        STACKED ([S, B, ...] leaves) for rollback by re-selection
        (``slots.select_verify_step``).
        Returns (logits [B, W, V], new caches)."""
        x = self._embed(params, tokens)
        x, _, new_caches = self._stack(params, x, rt, caches=caches,
                                       active=active, verify_window=True)
        return self._head(params, x, rt), new_caches
