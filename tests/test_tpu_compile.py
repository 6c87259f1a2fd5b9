"""Compile the serving path's Pallas kernels at granite-3-8b widths for a
TPU v5e that is described, not attached.

Interpret mode (every other kernel test) accepts what the chip's compiler
refuses — a shift of a uint8 vector, a block that overflows the scoped
VMEM — so these compiles, with ``interpret=False``, guard the kernels at
the widths the chip smoke serves.  The topology is described inside a
fixture: only the test worker that runs this file loads the TPU compiler.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import act_quant as aq
from repro.kernels import bitserial_matmul as bsm
from repro.kernels import grouped_matmul as gmm
from repro.kernels import kv_attention as kva
from repro.kernels import ops

D_MODEL, D_FF = 4096, 12800     # granite-3-8b (configs/granite_3_8b.py)
DECODE_ROWS = 8                 # one decode step of an 8-slot batch
PREFILL_ROWS = 512


@pytest.fixture(scope="module")
def v5e():
    """The described v5e:2x2 topology (four chips)."""
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache; keep the cache out of these compiles.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text       # the kernel itself, compiled
    return text


@pytest.mark.parametrize("packed", [False, True], ids=["unpacked", "packed"])
@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL)],
                         ids=["up", "down"])
def test_grouped_dequant_matmul_compiles(one_chip, packed, k, n):
    m = DECODE_ROWS
    w = ((k, n), jnp.uint8) if packed else ((4, k, n), jnp.int8)
    _compile(one_chip,
             lambda x, w, mult, xs, ws: gmm.grouped_dequant_matmul(
                 x, w, mult, xs, ws, nplanes=4, packed=packed, bm=m),
             ((m, k), jnp.int8), w, ((m, 4), jnp.int32),
             ((m, 1), jnp.float32), ((m, n), jnp.float32))


def test_packed_bitserial_matmul_compiles(one_chip):
    m = DECODE_ROWS
    _compile(one_chip,
             lambda x, w: bsm.packed_bitserial_matmul(
                 x, w, w_bits=8, eff_bits=4, bm=m),
             ((m, D_MODEL), jnp.int8), ((D_MODEL, D_FF), jnp.uint8))


def test_act_quant_rows_compiles_at_d_ff(one_chip):
    m, k = PREFILL_ROWS, D_FF
    bm = aq.block_rows(m, k)
    # Padded to whole blocks, as kernels/ops.py does.
    _compile(one_chip,
             lambda x, q: aq.act_quant_rows(ops._pad_to(x, bm, 0),
                                            ops._pad_to(q, bm, 0), bm=bm),
             ((m, k), jnp.float32), ((m, 1), jnp.float32))


def test_act_quant_compiles_at_d_ff(one_chip):
    m, k = PREFILL_ROWS, D_FF
    bm = aq.block_rows(m, k)
    _compile(one_chip,
             lambda x: aq.act_quant(ops._pad_to(x, bm, 0), bm=bm),
             ((m, k), jnp.float32))


# The decode attention kernel at granite-3-8b's cache: 16 slots, 8 KV heads
# of 256 byte lanes, 32 query heads of head_dim 128.
B_KV, KVH, LANES, H, DH = 16, 8, 256, 32, 128
# The longest cache whose scratch fits kva.MAX_VMEM at these heads.
S_MAX = max(s for s in range(kva.BLOCK, 65536, kva.BLOCK)
            if kva.tiles_on_tpu(s, H, KVH, DH))


def _kv_shapes(s, h=H, kvh=KVH):
    return (((B_KV, h, DH), jnp.bfloat16),
            ((B_KV, s, kvh, LANES), jnp.uint8),
            ((B_KV, s, kvh, LANES), jnp.uint8),
            ((B_KV, s, kvh, 1), jnp.bfloat16),
            ((B_KV, s, kvh, 1), jnp.bfloat16),
            ((B_KV,), jnp.int32), ((B_KV,), jnp.int32))


@pytest.mark.parametrize("s", [2560, 8192, S_MAX],
                         ids=["cell", "long", "longest"])
def test_kv_decode_attention_compiles(one_chip, s):
    """The benchmark cell's decode attention over the (16, 4) mixed arena
    (and 8192 positions, past the default scoped VMEM, and the longest
    cache the kernel admits).  The arena reaches the kernel as it is laid
    out: no copy or transpose of it (or of its scale rows) feeds the
    call."""
    text = _compile(
        one_chip,
        lambda q, k, v, ks, vs, n, bits: kva.kv_decode_attention(
            q, k, v, ks, vs, n, bits, tiers=(16, 4)),
        *_kv_shapes(s))
    moved = [line for line in text.splitlines()
             if f"[{B_KV},{s}," in line and (" copy(" in line
                                             or " transpose(" in line)]
    assert not moved, moved


def test_kv_decode_attention_compiles_in_shard_map(v5e):
    """Four-way tensor-parallel serving (``chip_smoke.py --mesh4``): inside
    ``shard_map`` over the described v5e:2x2 the kernel gets each chip's
    heads, 8 query and 2 KV heads of the (16, 8, 4) mixed arena."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(v5e.devices), ("model",))
    heads, kv = P(None, "model", None), P(None, None, "model", None)
    specs = (heads, kv, kv, kv, kv, P(None), P(None))
    seen = []

    def local(q, k, v, ks, vs, n, bits):
        seen.append((q.shape[1], k.shape[2]))
        return kva.kv_decode_attention(q, k, v, ks, vs, n, bits,
                                       tiers=(16, 8, 4))

    fn = jax.shard_map(local, mesh=mesh, in_specs=specs, out_specs=heads,
                       check_vma=False)        # as the engine's programs
    args = [jax.ShapeDtypeStruct(shape, d,
                                 sharding=NamedSharding(mesh, spec))
            for (shape, d), spec in zip(_kv_shapes(2560), specs)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert seen == [(H // 4, KVH // 4)]
