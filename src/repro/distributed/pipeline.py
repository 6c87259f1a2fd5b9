"""Pipeline parallelism: GPipe-style microbatched execution over a "stage"
mesh axis using shard_map + collective_permute.

Orthogonal to the DP x TP production mesh (the dry-run uses 2D/3D meshes);
provided as the PP building block for depth-dominated models and validated
against sequential execution in tests (on fake CPU devices).
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


StageFn = Callable[[Any, jax.Array], jax.Array]


def gpipe(stage_fn: StageFn, *, axis_name: str = "stage") -> StageFn:
    """Build a pipelined forward for ``y = stage_{S-1}(... stage_0(x))``.

    stage_fn(stage_params, x) -> y must be shape-preserving ([mb, ...] -> same),
    and is executed with this device's stage parameters.

    Returns pipe(stage_params_local, x_micro [n_micro, mb, ...]) to be called
    INSIDE shard_map(..., in_specs=(P('stage'), P(None))): every device sees
    all microbatches, computes only its stage, and activations flow stage ->
    stage+1 through collective_permute.  Output: [n_micro, mb, ...] valid on
    the last stage (replicated back by the caller if needed).
    """

    def pipe(stage_params: Any, x_micro: jax.Array) -> jax.Array:
        n_stages = jax.lax.psum(1, axis_name)
        stage = jax.lax.axis_index(axis_name)
        n_micro = x_micro.shape[0]
        total = n_micro + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        buf = jnp.zeros_like(x_micro)                   # collected outputs
        carry = jnp.zeros_like(x_micro[0])              # inbound activation

        def tick(t: Any, state: Tuple[jax.Array, jax.Array]
                 ) -> Tuple[jax.Array, jax.Array]:
            carry, buf = state
            # Stage 0 injects microbatch t (when still available).
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inject = jnp.where(t < n_micro, 1, 0)
            x_in = jnp.where((stage == 0) & (inject == 1),
                             x_micro[mb_idx], carry)
            y = stage_fn(stage_params, x_in)
            # Last stage banks microbatch (t - (n_stages-1)) when valid.
            out_idx = t - (n_stages - 1)
            valid_out = (stage == n_stages - 1) & (out_idx >= 0)
            safe_idx = jnp.clip(out_idx, 0, n_micro - 1)
            buf = jnp.where(valid_out,
                            buf.at[safe_idx].set(y), buf)
            # Ship activations to the next stage.
            carry = jax.lax.ppermute(y, axis_name, perm)
            return carry, buf

        state = jax.lax.fori_loop(0, total, tick, (carry, buf))
        out: jax.Array = state[1]
        return out

    return pipe


def run_pipeline(mesh: Mesh, stage_fn: StageFn, stage_params: Any,
                 x_micro: jax.Array,
                 axis_name: str = "stage") -> jax.Array:
    """Convenience wrapper: shard_map the gpipe over ``axis_name``.

    stage_params: pytree with leading stage dim; x_micro: [n_micro, mb, ...].
    Returns the last stage's outputs, gathered to all devices."""
    pipe = gpipe(stage_fn, axis_name=axis_name)

    def shmapped(sp: Any, xm: jax.Array) -> jax.Array:
        out = pipe(jax.tree.map(lambda a: a[0], sp), xm)
        # Broadcast the final stage's buffer to every stage.
        n_stages = jax.lax.psum(1, axis_name)
        stage = jax.lax.axis_index(axis_name)
        mask = (stage == n_stages - 1).astype(out.dtype)
        summed: jax.Array = jax.lax.psum(out * mask, axis_name)
        return summed

    f = jax.shard_map(shmapped, mesh=mesh,
                      in_specs=(P(axis_name), P()), out_specs=P(),
                      check_vma=False)
    y: jax.Array = f(stage_params, x_micro)
    return y
