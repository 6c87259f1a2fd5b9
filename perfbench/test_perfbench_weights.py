"""The benchmark's weights: one jitted call for the program's stacked tree,
the same numbers layer by layer for the reference, a rule for every leaf
kind of every architecture the program has, and the shapes a configuration
file states by layer kind."""
import dataclasses
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import tiny, weights

SEED = 2**35 + 11


def _model(arch="qwen3-8b", **kw):
    from repro.configs import reduced_config
    from repro.models.transformer import LM
    cfg = dataclasses.replace(reduced_config(arch), **kw)
    return LM(cfg)


def test_stacked_tree_equals_layer_by_layer():
    params = _assert_regenerates(_model(num_layers=3))
    assert all(leaf.dtype == jnp.bfloat16
               for leaf in jax.tree_util.tree_leaves(params))


@pytest.mark.parametrize("arch", ["jamba-1.5-large-398b", "mamba2-1.3b"])
def test_hybrid_and_ssm_trees_equal_layer_by_layer(arch):
    _assert_regenerates(_model(arch))


def _assert_regenerates(model):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    seed = SEED
    params = weights.make_params(shapes, seed)
    base = weights.base_key(seed)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        p = weights.path_str(path)
        assert leaf.dtype == shapes_dtype(shapes, path)
        if p.startswith(weights.STACKED_PREFIX):
            for layer in range(leaf.shape[0]):
                want = weights.layer_leaf(base, p, leaf.shape[1:], leaf.dtype,
                                          layer)
                np.testing.assert_array_equal(np.asarray(leaf[layer]),
                                              np.asarray(want))
        elif p.endswith("/w") or p == "embed/emb":
            want = weights.leaf_value(weights.leaf_key(base, p), p,
                                      leaf.shape, leaf.dtype)
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want))
    return params


def shapes_dtype(shapes, path):
    leaf = shapes
    for k in path:
        leaf = leaf[k.key]
    return leaf.dtype


@pytest.mark.parametrize("arch", sorted(__import__(
    "repro.configs", fromlist=["ARCHS"]).ARCHS))
def test_every_preset_builds(arch):
    """Every architecture's smoke preset gets every leaf from a rule:
    finite, and Mamba-2's per-head leaves in their stated ranges."""
    model = _model(arch)
    params = weights.make_params(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)), SEED)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    lo, hi = (math.log(math.expm1(v)) for v in weights.DT_RANGE)
    seen = set()
    for path, leaf in flat:
        name = weights.path_str(path).rsplit("/", 1)[-1]
        v = np.asarray(leaf, np.float32)
        assert np.isfinite(v).all(), path
        seen.add(name)
        if name == "A_log":
            assert v.min() >= 0.0 and v.max() <= math.log(16.0) + 1e-6
            assert v.max() - v.min() > 0.1
        elif name == "dt_bias":
            assert v.min() >= lo - 1e-4 and v.max() <= hi + 1e-4
            dt = np.log1p(np.exp(v))
            assert dt.min() >= 1e-3 * (1 - 1e-4) and dt.max() <= 1e-1
        elif name == "D":
            assert (v == 1.0).all()
        elif name == "conv_w":
            assert 0.0 < np.abs(v).max() <= 1 / math.sqrt(v.shape[-2])
    if model.cfg.ssm:
        assert {"A_log", "dt_bias", "D", "conv_w", "conv_b"} <= seen


def test_a_one_dimensional_weight_has_no_fan_in():
    with pytest.raises(ValueError, match="fan-in"):
        weights.leaf_value(weights.base_key(1), "periods/pos0/x/w", (8,),
                           jnp.float32)


def _digest(named):
    h = hashlib.sha256()
    for name, a in named:
        h.update(name.encode())
        h.update(np.asarray(a, np.float32).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("tied,want", [
    (True, "cb32ad17c928ec3c2bcc66e02f82a46f39b0b487b1f05f75f5893edf7aa74d40"),
    (False, "3aed268121f60214ccbd6746364f16e08b4ab01bd33c9b9dfb05325bbf06ff2e"),
])
def test_granite_leaves_keep_their_bits(tied, want):
    """Digests taken before the Mamba-2 and expert rules were added: the
    granite smoke tree, and full-size granite-3-8b leaves of layer 15."""
    model = _model("granite-3-8b", num_layers=3, tie_embeddings=tied)
    params = weights.make_params(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)), SEED)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    assert _digest((weights.path_str(p), v) for p, v in flat) == want


def test_full_size_granite_leaves_keep_their_bits():
    import json
    import os
    with open(os.path.join(os.path.dirname(__file__), "configs",
                           "granite-3-8b.json")) as f:
        cfg = json.load(f)
    base = weights.base_key(SEED)
    named = [(n, weights.layer_leaf(base, "periods/pos0/" + n, shape,
                                    jnp.bfloat16, 15))
             for n, shape in sorted(weights.layer_shapes(cfg).items())
             if n.endswith("/g") or n in ("attn/q_proj/w", "attn/k_proj/w")]
    assert _digest(named) == \
        "d8cc7cc7b78e9ec893a8a946be321247c695f5ac3ba275b600db2078a40fa314"


def test_large_seeds_are_distinct_and_scales_follow_fan_in():
    a = weights.layer_leaf(weights.base_key(5), "periods/pos0/mlp/up_proj/w",
                           (64, 128), jnp.float32, 0)
    b = weights.layer_leaf(weights.base_key(5 + 2**33),
                           "periods/pos0/mlp/up_proj/w", (64, 128),
                           jnp.float32, 0)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(a).max()) <= 1 / 8 and float(jnp.abs(a).max()) > 0.1
    g = weights.layer_leaf(weights.base_key(5), "periods/pos0/ff_norm/g",
                           (64,), jnp.float32, 1)
    assert float(g.min()) == float(g.max()) == 1.0


def test_layer_shapes_match_the_program_tree():
    model = _model()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    c = model.cfg
    cfg = {"hidden_size": c.d_model, "num_attention_heads": c.num_heads,
           "num_key_value_heads": c.num_kv_heads, "head_dim": c.head_dim,
           "intermediate_size": c.d_ff, "qk_norm": c.qk_norm}
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes["periods"]["pos0"])
    program = {weights.path_str(p): s.shape[1:] for p, s in flat}
    assert program == weights.layer_shapes(cfg)


def test_layer_shapes_by_kind_match_the_program_tree():
    """A hybrid file's layer list (seven Mamba-2 layers, then attention,
    experts and a shared expert in each) states the program's stacked tree,
    and each layer's leaves sit at ``layer_path``."""
    from repro.models.transformer import LM
    cfg = tiny.HYBRID
    model = LM(tiny.arch_config(cfg))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes["periods"])
    program = {"periods/" + weights.path_str(p): s.shape for p, s in flat}
    assert program == weights.stack_shapes(cfg)
    assert weights.layer_path(cfg, 7) == ("periods/pos7/", 0)
    assert weights.layer_shapes(cfg, 0)["mamba/in_proj/w"] == (
        64, 2 * 128 + 2 * 16 + 8)
    assert weights.layer_shapes(cfg, 7)["moe/router/w"] == (64, 4)
    dense = dict(tiny.CONFIG, num_hidden_layers=4)
    assert weights.period(weights.layer_kinds(dense)) == 1
    assert weights.layer_path(dense, 3) == ("periods/pos0/", 3)
