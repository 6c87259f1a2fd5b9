"""Manual tensor-parallel matmuls with QUANTIZED collectives (shard_map).

GSPMD places resharding collectives at the consuming op — on XLA:CPU that is
the f32-promoted dot operand, so the gathers move f32 and an int8 tensor
upstream does not help (§Perf iterations J3/L1, refuted under pjit).  This
module takes explicit control with the classic Megatron column/row-parallel
pair, using the paper's activation quantization as the *wire format*:

  column-parallel (W N-sharded):   y_n = gather_int8(x_sp) @ W[:, n]
  row-parallel (W K-sharded):      y_sp = psum_scatter_bf16(x_n @ W[k_n, :])

The all-gather moves int8 codes + per-row bf16 scales — 4x fewer bytes than
the f32 gather GSPMD emits on CPU (2x fewer than native-bf16 TPU); the
reduce moves bf16 scattered partials — 8x fewer than an f32 all-reduce.

Numerically validated against the unsharded reference on fake devices
(tests/test_tp_matmul.py).  Complements `compression.py` (DP gradients): the
same decomposition idea pointed at the TP axis.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ops


def _quantize_rows(x: jax.Array,
                   bits: int = 8) -> Tuple[jax.Array, jax.Array]:
    """Wire quantizer == compute quantizer.

    Routes through the shared kernels/act_quant implementation
    (``ops.quantize_activations`` — Pallas kernel on TPU, bit-identical
    jnp oracle elsewhere) so the wire format and the compute format cannot
    drift, and inherits the reciprocal-multiply scale (``ref.quant_scale``)
    whose bits are stable across eager/jit."""
    q, scale = ops.quantize_activations(x.astype(jnp.float32), a_bits=bits,
                                        signed=True)
    return q, scale.astype(jnp.bfloat16)


def column_parallel_quantized(x_sp: jax.Array, w_ncol: jax.Array, *,
                              axis_name: str) -> jax.Array:
    """INSIDE shard_map: y_n = full(x) @ W_ncol with an int8 gather.

    x_sp:   [..., K/n]  sequence/hidden-sharded activations (SP form).
    w_ncol: [K, N/n]    column-sharded weight.
    Returns [..., N/n].
    """
    q, scale = _quantize_rows(x_sp)
    # Gather int8 shards; tiled=True concatenates along the axis -> [..., K].
    q_all: jax.Array = jax.lax.all_gather(q, axis_name, axis=q.ndim - 1,
                                          tiled=True)
    s_all: jax.Array = jax.lax.all_gather(scale, axis_name,
                                          axis=scale.ndim - 1,
                                          tiled=True)       # [..., n]
    k_shard = x_sp.shape[-1]
    # Per-source-shard dequantization: expand scales across their K/n block.
    s_full = jnp.repeat(s_all, k_shard, axis=-1)            # [..., K]
    x_full = q_all.astype(jnp.bfloat16) * s_full
    return jnp.matmul(x_full, w_ncol.astype(jnp.bfloat16))


def row_parallel_scatter(x_n: jax.Array, w_krow: jax.Array, *,
                         axis_name: str) -> jax.Array:
    """INSIDE shard_map: y_sp = psum_scatter(x_n @ W_krow) in bf16.

    x_n:    [..., N/n]  column-sharded activations (this device's slice).
    w_krow: [N/n, K]    row-sharded weight (matching slice).
    Returns [..., K/n]  (SP-sharded output).
    """
    partial = jnp.matmul(x_n.astype(jnp.bfloat16),
                         w_krow.astype(jnp.bfloat16))       # [..., K]
    out: jax.Array = jax.lax.psum_scatter(partial, axis_name,
                                          scatter_dimension=partial.ndim - 1,
                                          tiled=True)
    return out


def tp_mlp_block(mesh: Mesh, x: jax.Array, w_up: jax.Array,
                 w_down: jax.Array, *, axis_name: str = "model",
                 activation: Callable[[jax.Array], jax.Array]
                 = jax.nn.gelu) -> jax.Array:
    """y = act(x @ w_up) @ w_down with quantized manual-TP collectives.

    x: [..., D] replicated on `axis_name`; w_up: [D, F]; w_down: [F, D].
    Returns [..., D] replicated (for comparison against the reference)."""
    n = int(mesh.shape[axis_name])
    d, f = w_up.shape
    assert d % n == 0 and f % n == 0

    def body(x_sp: jax.Array, w_up_loc: jax.Array,
             w_down_loc: jax.Array) -> jax.Array:
        h = column_parallel_quantized(x_sp, w_up_loc, axis_name=axis_name)
        h = activation(h.astype(jnp.float32)).astype(jnp.bfloat16)
        y_sp = row_parallel_scatter(h, w_down_loc, axis_name=axis_name)
        y: jax.Array = jax.lax.all_gather(y_sp, axis_name,
                                          axis=y_sp.ndim - 1, tiled=True)
        return y

    lead = tuple([None] * (x.ndim - 1))
    fm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(*lead, axis_name),       # x: SP on last dim
                  P(None, axis_name),        # w_up: N-sharded
                  P(axis_name, None)),       # w_down: K-sharded
        out_specs=P(),
        check_vma=False)
    out: jax.Array = fm(x, w_up, w_down)
    return out


def collective_bytes_per_token(d: int, f: int,
                               n_shards: int) -> Dict[str, float]:
    """Napkin math for §Perf: wire bytes per token for one MLP block."""
    gather_int8 = d * 1 + (d // (d // n_shards)) * 2        # codes + scales
    gather_f32 = d * 4                                      # GSPMD on CPU
    gather_bf16 = d * 2                                     # native-TPU GSPMD
    scatter_bf16 = d * 2                                    # psum_scatter
    allreduce_f32 = d * 4 * 2                               # AR moves ~2x
    return {
        "gather_int8": gather_int8,
        "vs_f32": gather_f32 / gather_int8,
        "vs_bf16": gather_bf16 / gather_int8,
        "reduce_scatter_bf16": scatter_bf16,
        "vs_allreduce_f32": allreduce_f32 / scatter_bf16,
    }
