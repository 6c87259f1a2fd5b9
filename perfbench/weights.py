"""Seeded random weights, made by the benchmark and never by the program.

Every leaf of a dense GQA model is a pure function of ``(seed, path,
layer)``: the program's stacked ``[layers, ...]`` tree is made in one jitted
call on the device, and the plain reference regenerates one layer at a time
from the same function, so the two see the same numbers without the
reference ever reading an array the program holds.

Paths follow the program's parameter tree (``periods/pos0/attn/q_proj/w``);
the reference names them itself.  Values are uniform, so the map from random
bits to floats is exact arithmetic and gives the same bits on every device:

* ``.../g`` (norm gains): ones;
* ``embed/emb``: uniform with standard deviation 0.02;
* ``.../w`` (projections and the untied head): uniform in
  ``[-1/sqrt(fan_in), 1/sqrt(fan_in))``.
"""
from __future__ import annotations

import math
import zlib
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

STACKED_PREFIX = "periods/"
EMBED_STD = 0.02


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed (all 64 bits count)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def leaf_key(base: jax.Array, path: str) -> jax.Array:
    """The key of the leaf at ``path`` under a seed's ``base_key``."""
    return jax.random.fold_in(base, zlib.crc32(path.encode()))


def leaf_value(key: jax.Array, path: str, shape: Sequence[int],
               dtype: Any) -> jax.Array:
    """One layer's value of the leaf at ``path`` (``shape`` without the
    stacked layer axis)."""
    name = path.rsplit("/", 1)[-1]
    if name == "g":
        return jnp.ones(shape, dtype)
    if path == "embed/emb":
        bound = EMBED_STD * math.sqrt(3.0)
    elif name == "w":
        bound = 1.0 / math.sqrt(shape[-2])
    else:
        raise ValueError(f"no rule for a weight at {path!r}")
    return jax.random.uniform(key, tuple(shape), jnp.float32,
                              -bound, bound).astype(dtype)


def layer_leaf(base: jax.Array, path: str, shape: Sequence[int], dtype: Any,
               layer: Any) -> jax.Array:
    """Layer ``layer`` of a stacked leaf (what the reference regenerates)."""
    return leaf_value(jax.random.fold_in(leaf_key(base, path), layer),
                      path, shape, dtype)


def path_str(path: Tuple[Any, ...]) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def make_params(shapes: Any, seed: int) -> Any:
    """The program's float parameter tree, filled from ``seed``, made on the
    device in one jitted call.  ``shapes`` is the tree of
    ``jax.ShapeDtypeStruct`` that ``jax.eval_shape(model.init, key)`` gives;
    leaves under ``periods/`` carry the stacked layer axis first."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [(path_str(p), s.shape, s.dtype) for p, s in flat]

    def build(key: jax.Array) -> Any:
        leaves = []
        for path, shape, dtype in specs:
            if path.startswith(STACKED_PREFIX):
                leaves.append(jax.vmap(
                    lambda i, path=path, shape=shape, dtype=dtype:
                    layer_leaf(key, path, shape[1:], dtype, i))(
                        jnp.arange(shape[0])))
            else:
                leaves.append(leaf_value(leaf_key(key, path), path, shape,
                                         dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(base_key(seed))


def layer_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    """Per-layer leaf shapes of a dense GQA block with a SwiGLU MLP, keyed by
    path under ``periods/pos0/`` (the reference's own statement of them)."""
    d = cfg["hidden_size"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, ff = cfg["head_dim"], cfg["intermediate_size"]
    shapes = {
        "mixer_norm/g": (d,),
        "attn/q_proj/w": (d, h * dh),
        "attn/k_proj/w": (d, kvh * dh),
        "attn/v_proj/w": (d, kvh * dh),
        "attn/o_proj/w": (h * dh, d),
        "ff_norm/g": (d,),
        "mlp/gate_proj/w": (d, ff),
        "mlp/up_proj/w": (d, ff),
        "mlp/down_proj/w": (ff, d),
    }
    if cfg.get("qk_norm"):
        shapes["attn/q_norm/g"] = (dh,)
        shapes["attn/k_norm/g"] = (dh,)
    return shapes
