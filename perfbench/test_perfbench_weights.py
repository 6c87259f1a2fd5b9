"""The benchmark's weights: one jitted call for the program's stacked tree,
the same numbers layer by layer for the reference."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights


def _model(**kw):
    from repro.configs import reduced_config
    from repro.models.transformer import LM
    cfg = dataclasses.replace(reduced_config("qwen3-8b"), num_layers=3, **kw)
    return LM(cfg)


def test_stacked_tree_equals_layer_by_layer():
    model = _model()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    seed = 2**35 + 11
    params = weights.make_params(shapes, seed)
    base = weights.base_key(seed)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        p = weights.path_str(path)
        assert leaf.dtype == jnp.bfloat16
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
