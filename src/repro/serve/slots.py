"""Fixed-slot cache arena for continuous batching.

The model's cache pytree (``LM.init_cache``) stacks every leaf as
``[n_periods, B, ...]``: axis 1 is the slot axis.  This module provides the
slot-granular views the engine needs — extract one slot as a batch-1 cache,
write a batch-1 cache back into its slot, reset a slot — all as pure
functions usable under ``jax.jit`` with a TRACED slot index, so admitting a
request into slot ``i`` never touches any other slot's K/V rows, lengths,
or SSM state.

Besides the device-side cache pytree the arena keeps a host-side per-slot
**tier vector** (``SlotArena.tiers``): which precision tier currently
occupies each slot.  The engine derives the per-step mixed-tier group
layout (a jit-static tuple) from it, while the matching per-slot KV
precision lives ON DEVICE as traced data (``KVCache.kv_bits``, set at
admission via :func:`fill_kv_tier`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import KVCache
from repro.models.ssm import SSMCache

SLOT_AXIS = 1   # cache leaves are [n_periods, B, ...]


def slot_view(caches: Any, slot: Any) -> Any:
    """Extract slot ``slot`` as a batch-1 cache pytree (traced-index ok).

    Slicing EVERY leaf on the slot axis makes the view self-contained: the
    KV tier codes and per-period lengths ride along with the lanes, so the
    same view doubles as the preemption snapshot (``ServeEngine.preempt``)
    — restoring it into ANY free slot via :func:`slot_write` reproduces
    the suspended request's decode state exactly, whatever its KV tier."""
    with jax.named_scope("slot_io"):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1,
                                                   axis=SLOT_AXIS),
            caches)


def slot_write(caches: Any, sub: Any, slot: Any) -> Any:
    """Write a batch-1 cache pytree back into slot ``slot`` (the KV
    migration scratch path and the preemption restore path)."""
    def put(a: Any, s: Any) -> Any:
        idx = [0] * a.ndim
        idx[SLOT_AXIS] = slot
        return jax.lax.dynamic_update_slice(a, s.astype(a.dtype), tuple(idx))
    with jax.named_scope("slot_io"):
        return jax.tree.map(put, caches, sub)


def slot_reset(caches: Any, slot: Any) -> Any:
    """Zero one slot's cache state (lengths included) in place of the pytree."""
    with jax.named_scope("slot_io"):
        zero = jax.tree.map(lambda a: jnp.zeros_like(
            jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=SLOT_AXIS)),
            caches)
        return slot_write(caches, zero, slot)


def merge_slots(updated: Any, original: Any, keep_original: Any) -> Any:
    """Per-slot cache merge: the masked twin of :func:`slot_write`.

    Rows where ``keep_original[b]`` is True come back with their ORIGINAL
    state on every leaf; other rows keep ``updated``.  This is the
    draft-discard half of speculative rollback: after the draft phase the
    speculative slots' low-tier KV/SSM writes are dropped wholesale
    (their lanes rewind to the pre-round state) while the plain slots in
    the same batch keep their real decode progress.  Both trees must be
    the arena layout (every leaf ``[n_periods, B, ...]``)."""
    batch = keep_original.shape[0]

    def one(new: Any, old: Any) -> Any:
        shape = (1, batch) + (1,) * (new.ndim - 2)
        return jnp.where(keep_original.reshape(shape), old, new)

    return jax.tree.map(one, updated, original)


def truncate_kv_lengths(caches: Any, rollback: Any, mask: Any) -> Any:
    """Masked KV length truncation: the masked-truncate twin of
    :func:`slot_view`.

    Shortens slot ``b``'s fill point by ``rollback[b]`` positions where
    ``mask[b]`` is True (traced-ok), leaving the K/V rows themselves in
    place: entries past the new length are invisible to
    ``decode_attention`` (its validity mask is ``pos < length``) and are
    overwritten by the next appends, so a length rewind IS the rollback.
    Used after the speculative verify forward to drop the KV of rejected
    draft positions.  No-op for SSM caches (their rollback is a state
    re-selection, :func:`select_verify_step`)."""

    def one(c: Any) -> Any:
        if isinstance(c, KVCache):
            delta = jnp.where(mask, rollback, 0).astype(c.length.dtype)
            shape = (1,) * (c.length.ndim - 1) + (-1,)
            return dataclasses.replace(
                c, length=jnp.maximum(c.length - delta.reshape(shape), 0))
        return c

    return jax.tree.map(one, caches,
                        is_leaf=lambda c: isinstance(c, (KVCache, SSMCache)))


def select_verify_step(caches: Any, step_index: Any) -> Any:
    """Collapse verify-stacked SSM caches to one step per slot.

    The multi-token verify forward returns SSM caches with a per-step
    window axis (leaves ``[n_periods, W, B, ...]`` — one conv/state
    snapshot per window position, because SSM state can only roll back
    by re-selection, not by a length rewind).  This picks snapshot
    ``step_index[b]`` (traced-ok int32 ``[B]``) for every slot and
    restores the arena layout ``[n_periods, B, ...]``.  Slots that were
    inactive during verify carry their pre-round state at every
    snapshot, so any index is correct for them."""

    def one(c: Any) -> Any:
        if isinstance(c, SSMCache):
            def sel(a: Any) -> Any:
                idx = step_index.reshape((1, 1, -1) + (1,) * (a.ndim - 3))
                return jnp.take_along_axis(a, idx.astype(jnp.int32),
                                           axis=1)[:, 0]
            return dataclasses.replace(c, conv=sel(c.conv),
                                       state=sel(c.state))
        return c

    return jax.tree.map(one, caches,
                        is_leaf=lambda c: isinstance(c, (KVCache, SSMCache)))


def fill_kv_tier(caches: Any, code: Any) -> Any:
    """Set every mixed-mode KVCache's per-slot tier lane(s) to ``code``.

    ``code`` is a (traced-ok) int32 tier code (16 = bf16, 8, 4).  Applied to
    a batch-1 slot view right before prefill, then written back with the
    rest of the slot state, so the admitted request's K/V rows quantize at
    ITS tier from the first prefill write on.  No-op for caches without
    per-slot tiers (SSM caches, homogeneous KV modes)."""
    def one(c: Any) -> Any:
        if isinstance(c, KVCache) and c.kv_bits is not None:
            return dataclasses.replace(
                c, kv_bits=jnp.zeros_like(c.kv_bits) + code)
        return c
    with jax.named_scope("slot_io"):
        return jax.tree.map(one, caches,
                            is_leaf=lambda c: isinstance(c, KVCache))


def migrate_kv_tier(caches: Any, slot: Any, code: Any) -> Any:
    """Requantize ONE slot's live KV lane at a new tier code, in place of
    the arena pytree (the KV half of mid-stream tier migration).

    ``slot`` and ``code`` (16 = bf16, 8, 4) are traced-ok int32 scalars, so
    one jitted instance serves every (slot, from-tier, to-tier) migration.
    The slot's lanes are dequantized at their CURRENT tier and re-encoded
    at ``code`` through :meth:`repro.models.layers.KVCache.requantize` —
    bit-identical to quantizing the dequantized cache directly at the
    target precision.  Lengths, SSM state and every other slot are
    untouched.  No-op for caches without per-slot tiers."""
    sub = slot_view(caches, slot)

    def one(c: Any) -> Any:
        if isinstance(c, KVCache) and c.mixed:
            return c.requantize(code)
        return c

    sub = jax.tree.map(one, sub, is_leaf=lambda c: isinstance(c, KVCache))
    return slot_write(caches, sub, slot)


class SlotArena:
    """Owns the arena cache pytree: ``max_slots`` persistent decode slots
    sharing one pre-allocated KV/SSM cache, each with an independent fill
    point (per-slot ``KVCache.length``).

    ``kv_bits`` follows :meth:`KVCache.create`: None / 8 / 4 for
    homogeneous storage, or a tuple of tier codes for the mixed per-slot
    arena.  ``tiers`` is the host-side slot -> tier-name vector the engine
    maintains at admit/release time (None = slot free)."""

    def __init__(self, model: Any, max_slots: int, max_len: int,
                 kv_bits: Any = None) -> None:
        self.max_slots = max_slots
        self.max_len = max_len
        self.kv_bits = kv_bits
        self.caches: Any = model.init_cache(max_slots, max_len,
                                            kv_bits=kv_bits)
        self.tiers: List[Optional[str]] = [None] * max_slots
