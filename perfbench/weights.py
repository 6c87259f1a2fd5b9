"""Seeded random weights, made by the benchmark and never by the program,
and the shapes a configuration file states for them.

Every leaf is a pure function of ``(seed, path, layer)``: the program's
stacked ``[periods, ...]`` tree is made in one jitted call on the device,
and a plain reference regenerates one layer at a time from the same
function, so the two see the same numbers without the reference ever
reading an array the program holds.

Paths follow the program's parameter tree: layer ``i`` of a file's layer
list is position ``i % period`` of period ``i // period``
(``periods/pos{i % period}/attn/q_proj/w``, index ``i // period``; see
``layer_path``); the reference names them itself.  Values are uniform, so
the map from random bits to floats is exact arithmetic and gives the same
bits on every device.  By the leaf's name:

* ``g`` (norm gains) and ``D`` (Mamba-2's skip, one per head): ones;
* ``embed/emb``: uniform with standard deviation 0.02;
* ``w`` (projections, expert weights ``[experts, K, N]``, the router and the
  untied head): uniform in ``[-1/sqrt(K), 1/sqrt(K))``, ``K = shape[-2]``;
* ``conv_w`` (Mamba-2's depthwise causal conv, ``[d_conv, channels]``):
  uniform in ``[-1/sqrt(d_conv), 1/sqrt(d_conv))``, the bound of a
  depthwise conv's default initialisation (fan-in ``d_conv``);
* ``conv_b``: zeros (its shape does not carry ``d_conv``, so the uniform
  bound could not be a function of the leaf alone);
* ``A_log``: ``log(A)``, ``A`` uniform in ``[1, 16)`` per head (Mamba-2's
  initialisation of the decay; the program takes ``-exp(A_log)``);
* ``dt_bias``: the inverse softplus ``dt + log(-expm1(-dt))`` of a step
  ``dt = exp(u)``, ``u`` uniform in ``[log 1e-3, log 1e-1)`` per head, so
  ``softplus(dt_bias)`` is log-uniform in ``[1e-3, 1e-1)`` (Mamba-2's
  initialisation of the step).

Mamba-2's ``A_log``, ``dt_bias`` and ``D`` and the router are float32 in the
program's tree; every other leaf is in the configuration's dtype.
"""
from __future__ import annotations

import math
import re
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

STACKED_PREFIX = "periods/"
EMBED_STD = 0.02
A_RANGE = (1.0, 16.0)            # Mamba-2: A uniform per head, A_log = log A
DT_RANGE = (1e-3, 1e-1)          # Mamba-2: softplus(dt_bias) log-uniform
MIXERS = {"attention": "attn", "mamba": "mamba"}    # layer_types -> program
# Keys that size a layer's leaves or count what is held, beside those that
# ``is_size`` knows by their ending.
SIZES = {"num_attention_heads", "num_key_value_heads", "mamba_d_state",
         "mamba_d_head", "mamba_n_heads", "mamba_n_groups", "mamba_d_conv",
         "mamba_expand", "num_hidden_layers", "padded_vocab"}


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
    shape = tuple(shape)
    if name in ("g", "D"):
        return jnp.ones(shape, dtype)
    if name == "conv_b":
        return jnp.zeros(shape, dtype)
    if name == "A_log":
        a = jax.random.uniform(key, shape, jnp.float32, *A_RANGE)
        return jnp.log(a).astype(dtype)
    if name == "dt_bias":
        lo, hi = (math.log(v) for v in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if path == "embed/emb":
        bound = EMBED_STD * math.sqrt(3.0)
    elif name == "w":
        if len(shape) < 2:
            raise ValueError(f"{path!r}: a weight of shape {shape} has no "
                             "fan-in axis")
        bound = 1.0 / math.sqrt(shape[-2])
    elif name == "conv_w":
        bound = 1.0 / math.sqrt(shape[0])
    else:
        raise ValueError(f"no rule for a weight at {path!r}")
    return jax.random.uniform(key, shape, jnp.float32,
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


def is_size(key: str) -> bool:
    """Whether a file's key sizes a layer or counts what is held: a width
    (no width is ever cut or departed from) or a count (cut only under
    ``reduced``, with the published number beside it)."""
    return key in SIZES or key.endswith(("_dim", "_rank", "_size")) or \
        "expert" in key


def served(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration as the program runs it: its published values with
    its ``departures["run"]`` laid over them.  A departure of a width or a
    count (``is_size``) is refused."""
    run = cfg.get("departures", {}).get("run", {})
    sizes = sorted(k for k in run if is_size(k))
    if sizes:
        raise ValueError(f"departures.run may not change {sizes}: a width "
                         "is run as published, a count held here is cut "
                         "under reduced")
    return {**cfg, **run}


def published(cfg: Dict[str, Any], key: str) -> Any:
    """The published value of ``key``: for a key the file cuts, the number
    its ``reduced`` entry states first (``"72 -> 18: why"``, the cut value
    after the arrow being the file's own); else the file's value."""
    if key in cfg.get("reduced", {}):
        return cut(cfg, key)[0]
    return cfg[key]


def cut(cfg: Dict[str, Any], key: str) -> Tuple[int, int]:
    """(published, held here) of a count the file cuts, read from its
    ``reduced`` entry, ``"<published> -> <held>: why"``."""
    m = re.match(r"\s*(\d+)\s*->\s*(\d+)\b", cfg["reduced"][key])
    if m is None:
        raise ValueError(f"reduced[{key!r}] does not begin "
                         f"'<published> -> <held>': {cfg['reduced'][key]!r}")
    return int(m.group(1)), int(m.group(2))


def layer_kind(cfg: Dict[str, Any], layer: int) -> Tuple[str, Optional[str]]:
    """(mixer, feed-forward) of layer ``layer`` in the program's names: the
    mixer from ``layer_types`` (``attention`` -> ``attn``, ``mamba``) where
    the file gives it, else attention; the feed-forward ``moe`` where the
    file gives ``num_local_experts``, else ``mlp`` of
    ``intermediate_size``, else none."""
    cfg = served(cfg)
    types = cfg.get("layer_types")
    ff = "moe" if cfg.get("num_local_experts") else \
        "mlp" if cfg.get("intermediate_size") else None
    return (MIXERS[types[layer]] if types else "attn"), ff


def layer_kinds(cfg: Dict[str, Any]) -> List[Tuple[str, Optional[str]]]:
    """``layer_kind`` of each of the ``num_hidden_layers`` layers the file
    holds (the first of its ``layer_types``)."""
    n = cfg["num_hidden_layers"]
    types = served(cfg).get("layer_types")
    if types is not None and len(types) < n:
        raise ValueError(f"layer_types lists {len(types)} layers, the file "
                         f"holds {n}")
    return [layer_kind(cfg, i) for i in range(n)]


def period(kinds: Sequence[Any]) -> int:
    """The shortest period of a layer list that tiles it whole (the
    program stacks its layers by period)."""
    n = len(kinds)
    return next(p for p in range(1, n + 1) if n % p == 0 and all(
        kinds[i] == kinds[i % p] for i in range(n)))


def layer_path(cfg: Dict[str, Any], layer: int) -> Tuple[str, int]:
    """(path prefix, index on the stacked axis) of layer ``layer``'s leaves:
    ``("periods/pos{layer % period}/", layer // period)``."""
    p = period(layer_kinds(cfg))
    return f"{STACKED_PREFIX}pos{layer % p}/", layer // p


def layer_shapes(cfg: Dict[str, Any], layer: int = 0
                 ) -> Dict[str, Tuple[int, ...]]:
    """Leaf shapes of layer ``layer`` of the file's layer list, by its kind,
    keyed by path under its ``layer_path`` prefix (the reference's own
    statement of them).  Attention: q/k/v/o (and per-head q/k norms with
    ``qk_norm``).  Mamba-2: ``in_proj`` to ``2*d_inner + 2*n_groups*d_state
    + n_heads`` (z, x, B, C, dt), the conv over ``d_inner +
    2*n_groups*d_state`` channels, ``A_log``/``dt_bias``/``D`` per head, the
    gated norm and ``out_proj``.  A SwiGLU MLP of ``intermediate_size``; or
    ``num_local_experts`` experts held here of that width, the router over
    the published count of experts, and a shared expert of
    ``shared_intermediate_size`` where the file gives one."""
    mixer, ff = layer_kind(cfg, layer)
    cfg = served(cfg)
    d = cfg["hidden_size"]
    shapes: Dict[str, Tuple[int, ...]] = {"mixer_norm/g": (d,)}
    if mixer == "attn":
        h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        dh = cfg["head_dim"]
        shapes.update({"attn/q_proj/w": (d, h * dh),
                       "attn/k_proj/w": (d, kvh * dh),
                       "attn/v_proj/w": (d, kvh * dh),
                       "attn/o_proj/w": (h * dh, d)})
        if cfg.get("qk_norm"):
            shapes["attn/q_norm/g"] = (dh,)
            shapes["attn/k_norm/g"] = (dh,)
    else:
        di = cfg["mamba_expand"] * d
        nh = cfg["mamba_n_heads"]
        conv = di + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
        shapes.update({"mamba/in_proj/w": (d, di + conv + nh),
                       "mamba/out_proj/w": (di, d),
                       "mamba/conv_w": (cfg["mamba_d_conv"], conv),
                       "mamba/conv_b": (conv,),
                       "mamba/A_log": (nh,), "mamba/dt_bias": (nh,),
                       "mamba/D": (nh,), "mamba/norm/g": (di,)})
    if ff is not None:
        shapes["ff_norm/g"] = (d,)
    if ff == "mlp":
        ffw = cfg["intermediate_size"]
        shapes.update({"mlp/gate_proj/w": (d, ffw), "mlp/up_proj/w": (d, ffw),
                       "mlp/down_proj/w": (ffw, d)})
    elif ff == "moe":
        e, w = cfg["num_local_experts"], cfg["intermediate_size"]
        shapes.update({
            "moe/router/w": (d, published(cfg, "num_local_experts")),
            "moe/gate_proj/w": (e, d, w), "moe/up_proj/w": (e, d, w),
            "moe/down_proj/w": (e, w, d)})
        sw = cfg.get("shared_intermediate_size")
        if sw:
            shapes.update({"moe/shared/gate_proj/w": (d, sw),
                           "moe/shared/up_proj/w": (d, sw),
                           "moe/shared/down_proj/w": (sw, d)})
    return shapes


def stack_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    """Every stacked leaf's path and shape (the period count first) of the
    program's tree for the file's layer list."""
    kinds = layer_kinds(cfg)
    p = period(kinds)
    return {f"{STACKED_PREFIX}pos{i}/{name}": (len(kinds) // p,) + shape
            for i in range(p) for name, shape in layer_shapes(cfg, i).items()}
