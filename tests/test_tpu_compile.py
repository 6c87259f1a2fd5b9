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
from repro.kernels import ops

D_MODEL, D_FF = 4096, 12800     # granite-3-8b (configs/granite_3_8b.py)
DECODE_ROWS = 8                 # one decode step of an 8-slot batch
PREFILL_ROWS = 512


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text       # the kernel itself, compiled


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
