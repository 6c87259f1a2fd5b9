"""The decode-attention kernel (``kernels.kv_attention``), interpreted on
the CPU, for every KV encoding — dense bf16, int8, int4 and the mixed
per-slot arena — with 1 and 4 query heads per KV head:

* it agrees with ``layers.decode_attention``'s jnp path to one bf16 ulp;
* positions at or past a slot's ``length`` may hold NaN/Inf bytes without
  changing a bit of the result, and the fetch index never passes a slot's
  last filled block;
* a mixed-arena slot at tier m equals the homogeneous cache at m bit for
  bit;
* an empty slot gives finite output;
* on the TPU path ``decode_attention`` calls it inside the ``attention``
  scope.

And the engine's KV read counters on a short CPU run.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core.policy import uniform_schedule
from repro.kernels import kv_attention as kva
from repro.kernels import ops
from repro.models import layers
from repro.models.layers import Runtime
from repro.models.transformer import LM
from repro.serve import Request, ServeEngine
from repro.telemetry import Telemetry
from repro.telemetry.xplane import scope_of
from test_layer_scopes import _ops

B, S, KVH, DH = 3, 384, 2, 16        # 3 position blocks of 128
LENGTHS = (5, 200, 0)                # part of block 0; into block 1; empty
ENCODINGS = {"bf16": None, "int8": 8, "int4": 4, "mixed-16-8-4": (16, 8, 4),
             "mixed-16-4": (16, 4)}
# The homogeneous cache each tier code stands for.
HOMOGENEOUS = {16: None, 8: 8, 4: 4}


def _cache(kv_bits, tiers=None, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    c = layers.KVCache.create(B, S, KVH, DH, kv_bits=kv_bits)
    if tiers is not None:
        c = dataclasses.replace(c, kv_bits=jnp.asarray(tiers, jnp.int32))
    kn = jax.random.normal(k1, (B, S, KVH, DH), jnp.bfloat16)
    vn = jax.random.normal(k2, (B, S, KVH, DH), jnp.bfloat16)
    return c.update(kn, vn, 0, new_length=jnp.asarray(LENGTHS, jnp.int32))


def _query(group, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (B, 1, KVH * group, DH), jnp.bfloat16)


def _mixed_tiers(kv_bits):
    """A tier code per slot that covers every mode of a mixed arena."""
    if not isinstance(kv_bits, tuple):
        return None
    return [kv_bits[i % len(kv_bits)] for i in range(B)][::-1]


def _kernel(q, c):
    out = kva.kv_decode_attention(q[:, 0], c.k, c.v, c.k_scale, c.v_scale,
                                  c.length, c.kv_bits, tiers=c.tiers,
                                  interpret=True)
    return np.asarray(out[:, None], np.float32)


def _poison(c):
    """Every stored byte at or past each slot's length set to 0xFF (a NaN
    bf16 pair, code -1 for int8), every scale there to +Inf."""
    past = (jnp.arange(S)[None, :] >= c.length[:, None])[..., None, None]

    def bad(a, fill):
        return jnp.where(past, jnp.asarray(fill, a.dtype), a)

    if c.k.dtype == jnp.bfloat16:
        fill = jnp.asarray(np.float32("nan"), jnp.bfloat16)
    else:
        fill = jax.lax.bitcast_convert_type(jnp.uint8(0xFF), c.k.dtype)
    out = dataclasses.replace(c, k=bad(c.k, fill), v=bad(c.v, fill))
    if c.k_scale is not None:
        out = dataclasses.replace(out, k_scale=bad(c.k_scale, np.inf),
                                  v_scale=bad(c.v_scale, np.inf))
    return out


groups = pytest.mark.parametrize("group", [1, 4])
encodings = pytest.mark.parametrize("kv_bits", list(ENCODINGS.values()),
                                    ids=list(ENCODINGS))


@encodings
@groups
def test_kernel_matches_decode_attention(kv_bits, group):
    """Same arithmetic as the jnp path; only the summation order inside a
    matmul may differ, which moves a bf16 output by at most one ulp."""
    c = _cache(kv_bits, _mixed_tiers(kv_bits))
    q = _query(group)
    ref = np.asarray(layers.decode_attention(q, c), np.float32)
    got = _kernel(q, c)
    filled = np.asarray(LENGTHS) > 0     # an empty slot is masked anyway
    np.testing.assert_allclose(got[filled], ref[filled], rtol=2 ** -7,
                               atol=1e-6)


@encodings
@groups
def test_positions_past_length_are_never_read(kv_bits, group):
    c = _cache(kv_bits, _mixed_tiers(kv_bits))
    q = _query(group)
    clean = _kernel(q, c)
    dirty = _kernel(q, _poison(c))
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)
    # The K/V index maps stop at each slot's last filled block.
    bs = kva.BLOCK
    for n in LENGTHS:
        last = max(-(-n // bs) - 1, 0)
        fetched = {int(kva.fetch_block(jnp.int32(n), j))
                   for j in range(S // bs)}
        assert fetched == set(range(last + 1)), (n, fetched)


@pytest.mark.parametrize("modes", [(16, 8, 4), (16, 4)], ids=str)
@groups
def test_mixed_slot_equals_homogeneous_cache(modes, group):
    q = _query(group)
    for tier in modes:
        mixed = _kernel(q, _cache(modes, [tier] * B))
        homog = _kernel(q, _cache(HOMOGENEOUS[tier]))
        np.testing.assert_array_equal(mixed, homog, err_msg=str(tier))


@encodings
@groups
def test_empty_slot_is_finite(kv_bits, group):
    """Also with the tier code 0 of a zeroed arena (``LM.init_cache``)."""
    tiers = _mixed_tiers(kv_bits)
    if tiers is not None:
        tiers[2] = 0
    c = _cache(kv_bits, tiers)
    out = _kernel(_query(group), c)
    assert LENGTHS[2] == 0 and np.isfinite(out[2]).all()


@encodings
@groups
def test_attention_scope_holds_the_kernel(kv_bits, group, monkeypatch):
    """With the TPU path taken (the kernel runs interpreted here), the
    decode step's attention is the kernel, inside the ``attention``
    scope, and it takes the cache as stored."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    c = layers.KVCache.create(B, 128, KVH, 128, kv_bits=kv_bits)
    q = jnp.zeros((B, 1, KVH * group, 128), jnp.bfloat16)
    assert layers.decode_kernel_engages(128, KVH * group, KVH, 128)
    from jax._src.lib import xla_client
    opts = xla_client._xla.HloPrintOptions()
    opts.print_metadata = True
    hlo = jax.jit(layers.decode_attention).lower(q, c).compiler_ir(
        "hlo").get_hlo_module().to_string(opts)
    ops_ = _ops(hlo)
    kernel = [name for _, name in ops_ if "kv_decode_attention" in name]
    assert kernel
    assert {scope_of(name) for name in kernel} == {"attention"}
    dots = [name for op, name in ops_ if op == "dot"]
    assert dots and all("kv_decode_attention" in n for n in dots), dots[:3]


# Granite's cache at its local head counts: one device (32/8), four-way
# tensor parallel (8/2); and what does not tile or fit.
@pytest.mark.parametrize("max_len,heads,kv_heads,head_dim,engages", [
    (2560, 32, 8, 128, True),
    (8192, 32, 8, 128, True),         # past the default scoped VMEM
    (2560, 8, 2, 128, True),
    (65536, 32, 8, 128, False),       # scratch past MAX_VMEM
    (65536, 8, 2, 128, True),         # the same cache, a quarter per device
    (2500, 32, 8, 128, False),        # not whole blocks of positions
    (2560, 32, 8, 64, False),         # not whole lane tiles
], ids=["cell", "long", "tp4", "too-long", "too-long-tp4", "ragged",
        "narrow"])
def test_shapes_that_do_not_fit_take_the_jnp_path(
        max_len, heads, kv_heads, head_dim, engages, monkeypatch):
    """The kernel engages by shape on the TPU only: whole blocks and lane
    tiles, and a scratch within MAX_VMEM (asked for where it passes the
    default scoped VMEM); a cache that does not fit takes the jnp path."""
    assert not layers.decode_kernel_engages(2560, 32, 8, 128)   # the CPU
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert layers.decode_kernel_engages(max_len, heads, kv_heads,
                                        head_dim) is engages
    if engages:
        limit = kva.vmem_limit(max_len, heads, kv_heads, head_dim)
        assert limit is None or kva.DEFAULT_VMEM < limit <= kva.MAX_VMEM


# ---------------------------------------------------------------- counters
TIERS = {"8/8": (8, 8), "2/2": (2, 2)}


def _engine(**kw):
    cfg = reduced_config("granite-3-8b")
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = uniform_schedule(TIERS, kv_tiers={"8/8": None, "2/2": 4})
    rt = Runtime(policy=sched.policy_for(), mode="serve", schedule=sched)
    return cfg, ServeEngine(model, params, rt, max_batch=2, max_len=512,
                            decode_chunk=4, **kw)


def _requests(cfg):
    rng = np.random.default_rng(7)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               size=4 + 3 * i),
                    max_new_tokens=6 + i, tier=list(TIERS)[i % 2])
            for i in range(3)]


def test_kv_read_counters_on_the_cpu_path():
    """On the jnp path decode reads the whole arena every step (share 1.0);
    the ``serve_`` twins follow the counters."""
    tele = Telemetry()
    cfg, eng = _engine(telemetry=tele)
    assert not eng._kv_kernel
    eng.run(_requests(cfg))
    st = eng.stats
    reserved = st.decode_steps * eng.max_batch * eng.max_len
    assert st.kv_positions_reserved == reserved > 0
    assert st.kv_positions_read == reserved
    for name in ("kv_positions_read", "kv_positions_reserved"):
        assert tele.registry.value("serve_" + name) == getattr(st, name)


def test_kv_read_counters_count_whole_filled_blocks():
    """With the kernel engaged the read count is each slot's filled prefix
    in whole 128-position blocks (one block for an empty slot); the
    fill points are the host's mirror of the device lengths."""
    cfg, eng = _engine()
    eng._kv_kernel = True              # the host accounting of the TPU path
    reqs = _requests(cfg)
    eng.run(reqs)
    # Every fill point stays under 128 here: one block per slot-step.
    st = eng.stats
    assert st.kv_positions_read == st.decode_steps * eng.max_batch * 128
    assert st.kv_positions_read * 4 == st.kv_positions_reserved
    # The mirror ends at each slot's last occupant's prompt + tokens - 1.
    kv = next(c for c in jax.tree.leaves(
        eng.arena.caches, is_leaf=lambda x: isinstance(x, layers.KVCache))
        if isinstance(c, layers.KVCache))
    np.testing.assert_array_equal(eng._kv_len, np.asarray(kv.length)[0])
