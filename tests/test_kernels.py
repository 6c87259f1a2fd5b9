"""Pallas kernels vs pure-jnp oracles (interpret mode): shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import decompose
from repro.core.policy import LayerPrecision
from repro.kernels import ops, ref
from repro.kernels.act_quant import act_quant
from repro.kernels.bitserial_matmul import (bitserial_matmul,
                                            packed_bitserial_matmul)


@pytest.mark.parametrize("w_bits", range(2, 9))
@pytest.mark.parametrize("shape", [(128, 256, 128), (256, 128, 256)])
def test_bitserial_matmul_all_bits(w_bits, shape):
    m, k, n = shape
    rng = np.random.default_rng(w_bits)
    lo, hi = decompose.weight_range(w_bits, True)
    w = rng.integers(lo, hi + 1, size=(k, n)).astype(np.int8)
    planes = decompose.decompose_weights(w, w_bits)
    x = rng.integers(-128, 128, size=(m, k)).astype(np.int8)
    got = bitserial_matmul(jnp.asarray(x), planes, w_bits=w_bits,
                           interpret=True)
    want = ref.bitserial_matmul_ref(jnp.asarray(x), planes, w_bits)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("w_bits", [2, 4, 6, 8])
@pytest.mark.parametrize("signed", [True, False])
def test_packed_kernel(w_bits, signed):
    rng = np.random.default_rng(w_bits)
    lo, hi = decompose.weight_range(w_bits, signed)
    w = rng.integers(lo, hi + 1, size=(256, 128))
    planes = decompose.decompose_weights(w, w_bits, signed=signed)
    packed = ops.pack_planes(planes, w_bits)
    x = rng.integers(-128, 128, size=(128, 256)).astype(np.int8)
    got = packed_bitserial_matmul(jnp.asarray(x), packed, w_bits=w_bits,
                                  signed=signed, interpret=True)
    assert np.array_equal(np.asarray(got),
                          x.astype(np.int64) @ w.astype(np.int64))
    # pack/unpack roundtrip
    assert np.array_equal(
        np.asarray(ops.unpack_planes(packed, w_bits, signed)),
        np.asarray(planes))


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("m,k", [(128, 64), (256, 512)])
def test_act_quant_kernel(bits, m, k):
    rng = np.random.default_rng(m)
    x = (rng.normal(size=(m, k)) * 3).astype(np.float32)
    q, s = act_quant(jnp.asarray(x), bits=bits, interpret=True)
    qr, sr = ref.act_quant_ref(jnp.asarray(x), bits=bits)
    assert np.array_equal(np.asarray(q), np.asarray(qr))
    assert np.allclose(np.asarray(s), np.asarray(sr))


def test_ops_matmul_pads_unaligned_shapes():
    """Wrapper handles shapes that do not tile by 128."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(5, 96)).astype(np.float32)
    w = rng.normal(size=(96, 80)).astype(np.float32)
    y_dec = ops.matmul(jnp.asarray(x), jnp.asarray(w),
                       LayerPrecision(4, 8, backend="decomposed"))
    y_pal = ops.matmul(jnp.asarray(x), jnp.asarray(w),
                       LayerPrecision(4, 8, backend="pallas"))
    assert y_dec.shape == (5, 80)
    assert np.array_equal(np.asarray(y_dec), np.asarray(y_pal))


def test_backend_consistency_quality():
    """All quantized backends approximate the dense matmul."""
    rng = np.random.default_rng(1)
    x = rng.normal(size=(8, 128)).astype(np.float32)
    w = rng.normal(size=(128, 64)).astype(np.float32)
    dense = x @ w
    for be in ("fake_quant", "decomposed", "pallas"):
        y = np.asarray(ops.matmul(jnp.asarray(x), jnp.asarray(w),
                                  LayerPrecision(8, 8, backend=be)))
        rel = np.abs(y - dense).max() / np.abs(dense).max()
        assert rel < 0.03, (be, rel)


def test_quantized_weight_prepare_roundtrip():
    rng = np.random.default_rng(2)
    w = rng.normal(size=(64, 32)).astype(np.float32)
    prec = LayerPrecision(w_bits=5, a_bits=8)
    qw = ops.prepare_weight(jnp.asarray(w), prec)
    assert qw.planes.shape == (2, 64, 32)          # 5-bit = 3-2 decomposition
    q = decompose.recompose_weights(qw.planes, 5)
    back = np.asarray(q).astype(np.float32) * np.asarray(qw.scale)
    # Odd widths keep round-to-nearest (half-LSB bound); even widths use
    # nested truncation, whose 1-LSB floor bound is covered by
    # tests/test_precision_tiers.py.
    assert np.abs(back - w).max() <= np.asarray(qw.scale).max() * 0.51 + 1e-6


def test_lower_precision_monotone_error():
    """More weight bits -> better approximation (sanity of the whole path)."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(16, 128)).astype(np.float32)
    w = rng.normal(size=(128, 64)).astype(np.float32)
    dense = x @ w
    errs = []
    for bits in (2, 4, 8):
        y = np.asarray(ops.matmul(jnp.asarray(x), jnp.asarray(w),
                                  LayerPrecision(bits, 8, backend="decomposed")))
        errs.append(np.abs(y - dense).mean())
    assert errs[0] > errs[1] > errs[2]


@pytest.mark.parametrize("eff", [2, 4, 6, 8])
def test_grouped_dequant_matmul_single_group(eff):
    """Fused dequant epilogue == (prefix-plane GEMM) * scales, bf16 out.

    Single-group degenerate case of the group-switching kernel (the mixed
    layouts are swept in test_grouped_kernel.py): the epilogue must apply
    x_scale [M,1] and per-row w_scale [M,N] exactly as the unfused
    ``acc.astype(f32) * xs * ws`` association does."""
    from repro.kernels import grouped_matmul as gmm
    rng = np.random.default_rng(eff)
    m, k, n = 128, 256, 128
    w = rng.normal(size=(k, n)).astype(np.float32)
    qw = ops.prepare_superplane(jnp.asarray(w))
    planes = qw.get_planes_msb()
    nplanes = decompose.num_prefix_planes(eff)
    plane_groups = ((m, nplanes),)
    mult = jnp.asarray(decompose.prefix_multipliers(plane_groups))
    x = rng.integers(-128, 128, size=(m, k)).astype(np.int8)
    xs = (rng.random((m, 1)) * 0.1 + 0.01).astype(np.float32)
    ws = (rng.random((1, n)) * 0.1 + 0.01).astype(np.float32)
    ws_rows = jnp.broadcast_to(jnp.asarray(ws), (m, n))
    got = gmm.grouped_dequant_matmul(
        jnp.asarray(x), planes[:nplanes], mult, jnp.asarray(xs), ws_rows,
        nplanes=nplanes, interpret=True)
    acc = decompose.decomposed_matmul_grouped(jnp.asarray(x), planes,
                                              ((m, eff),))
    want = (np.asarray(acc).astype(np.float32) * xs * ws).astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))


@pytest.mark.parametrize("backend", ["cpu", "tpu", "gpu"])
def test_pallas_mode_follows_backend(monkeypatch, backend):
    """Kernels interpret only on the CPU and run compiled on TPU; any other
    backend raises instead of silently interpreting."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if backend == "gpu":
        with pytest.raises(RuntimeError, match="gpu"):
            ops.act_quant_pallas(jnp.ones((8, 128), jnp.float32))
    else:
        assert ops._interpret() is (backend == "cpu")


@pytest.mark.parametrize("k", [64, 4096, 12800, 14336])
def test_act_quant_block_rows_fit_vmem(k):
    """Whole-row blocks shrink with K to stay within the VMEM budget, and
    a batch that fits one block stays one block."""
    from repro.kernels.act_quant import BLOCK_BYTES, block_rows
    bm = block_rows(4096, k)
    assert bm % 8 == 0 and 8 <= bm <= 128
    assert bm * k * 4 <= BLOCK_BYTES
    assert block_rows(5, k) == 5


def test_act_quant_multi_block_at_d_ff_matches_ref():
    """At K=12800 the row block (40) no longer covers the batch: the padded
    multi-block run is bit-identical to the unblocked oracle, for one width
    and for per-row widths."""
    rng = np.random.default_rng(3)
    x = jnp.asarray((rng.normal(size=(100, 12800)) * 2).astype(np.float32))
    q, s = ops.act_quant_pallas(x, a_bits=8)
    qr, sr = ref.act_quant_ref(x, bits=8)
    assert np.array_equal(np.asarray(q), np.asarray(qr))
    assert np.array_equal(np.asarray(s), np.asarray(sr))
    groups = ((60, LayerPrecision(8, 8, backend="pallas")),
              (40, LayerPrecision(4, 4, backend="pallas")))
    got = ops._quantize_activations_rows(x, groups, None, use_pallas=True)
    want = ops._quantize_activations_rows(x, groups, None, use_pallas=False)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))
