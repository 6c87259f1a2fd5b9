"""Tensor-parallel sharded serving (``ServeEngine(mesh=...)``).

Three layers of checks, mirroring the exactness argument in
docs/distributed.md:

* host-side algebra — plane-prefix truncation commutes with column
  sharding (all even bits x signedness x packed layouts), and the
  bit-serial wire pack/unpack is lossless and commutes with a tiled
  gather;
* spec rules — ``serve_tp_param_spec`` / ``serve_tp_cache_spec`` shard
  exactly the serve-TP projections and raise (never silently drop) on
  non-dividing axes;
* fake-device end-to-end — a 2-device mesh engine is token-identical to
  the unsharded engine across mixed 8/4/2 batches and a mid-stream
  ``set_tier`` migration, and the compiled decode step's all-gathers move
  int8 / bit-packed uint8 codes, not floats.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed import sharding_rules, tp_serve
from repro.kernels import ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(body: str, devices: int = 2) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


# ------------------------------------------------------ wire format (host)
@pytest.mark.parametrize("bits", [2, 4])
def test_wire_pack_roundtrip(bits):
    rng = np.random.default_rng(0)
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    q = jnp.asarray(rng.integers(lo, hi + 1, size=(3, 64)).astype(np.int8))
    p = tp_serve.wire_pack(q, bits)
    assert p.dtype == jnp.uint8
    assert p.shape == (3, 64 * bits // 8)
    assert np.array_equal(np.asarray(tp_serve.wire_unpack(p, bits)),
                          np.asarray(q))


@pytest.mark.parametrize("bits", [2, 4])
def test_wire_pack_commutes_with_tiled_gather(bits):
    """unpack(concat(pack(shard_i))) == concat(shard_i): packing is
    per-shard-contiguous, so a tiled all-gather of packed bytes decodes to
    the gather of the codes."""
    rng = np.random.default_rng(1)
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    shards = [jnp.asarray(rng.integers(lo, hi + 1, size=(2, 32))
                          .astype(np.int8)) for _ in range(4)]
    gathered_packed = jnp.concatenate(
        [tp_serve.wire_pack(s, bits) for s in shards], axis=-1)
    assert np.array_equal(
        np.asarray(tp_serve.wire_unpack(gathered_packed, bits)),
        np.asarray(jnp.concatenate(shards, axis=-1)))


def test_wire_bytes_per_element():
    assert tp_serve.wire_bytes_per_element(8) == 1.0
    assert tp_serve.wire_bytes_per_element(6) == 1.0
    assert tp_serve.wire_bytes_per_element(4) == 0.5
    assert tp_serve.wire_bytes_per_element(2) == 0.25
    assert tp_serve.wire_bytes_per_element(4, signed=False) == 1.0


# ------------------------------------- truncation commutes with sharding
@pytest.mark.parametrize("signed", [True, False])
@pytest.mark.parametrize("eff_bits", [2, 4, 6, 8])
@pytest.mark.parametrize("packed", [False, True])
def test_truncate_commutes_with_shard(eff_bits, signed, packed):
    """Plane-prefix truncation then column-sharding == sharding then
    truncation, bitwise — superplane codes and scales are per-COLUMN, so
    every tier mechanism works unchanged on an N-shard."""
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))
    qw = ops.prepare_superplane(w, signed=signed, packed=packed)
    trunc_full = ops.truncate_weight(qw, eff_bits)
    for n in (2, 4):
        for i in range(n):
            def col(a):
                step = a.shape[-1] // n
                return a[..., i * step:(i + 1) * step]

            shard = dataclasses.replace(
                qw,
                planes=None if packed else col(qw.planes),
                packed=col(qw.packed) if packed else None,
                scale=col(qw.scale))
            a = ops.truncate_weight(shard, eff_bits)   # shard -> truncate
            assert np.array_equal(np.asarray(a.scale),
                                  np.asarray(col(trunc_full.scale)))
            if packed:
                assert np.array_equal(np.asarray(a.packed),
                                      np.asarray(col(trunc_full.packed)))
            else:
                assert np.array_equal(np.asarray(a.planes),
                                      np.asarray(col(trunc_full.planes)))
            assert a.w_bits == trunc_full.w_bits


# ----------------------------------------------------------- spec rules
def test_serve_tp_param_spec_targets_and_raises():
    planes = jnp.zeros((4, 32, 16), jnp.int8)
    q_path = "['periods']['pos0']['attn']['q_proj']['w'].planes"
    spec = sharding_rules.serve_tp_param_spec(q_path, planes, n=2,
                                              kv_shards=True)
    assert spec == P(None, None, "model")
    # k/v shard only under kv_shards.
    k_path = "['periods']['pos0']['attn']['k_proj']['w'].scale"
    scale = jnp.zeros((1, 16), jnp.float32)
    assert sharding_rules.serve_tp_param_spec(
        k_path, scale, n=2, kv_shards=True) == P(None, "model")
    assert sharding_rules.serve_tp_param_spec(
        k_path, scale, n=2, kv_shards=False) == P()
    # Norms / embeddings / non-QW leaves: replicated.
    assert sharding_rules.serve_tp_param_spec(
        "['final_norm']['scale']", jnp.zeros((16,)), n=2,
        kv_shards=True) == P()
    # Exact-or-error: a non-dividing last axis raises.
    with pytest.raises(ValueError, match="does not divide"):
        sharding_rules.serve_tp_param_spec(
            q_path, jnp.zeros((4, 32, 15), jnp.int8), n=2, kv_shards=True)


def test_serve_tp_cache_spec_targets_and_raises():
    k = jnp.zeros((1, 2, 8, 4, 16), jnp.bfloat16)   # [periods,B,S,KVH,Dh]
    spec = sharding_rules.serve_tp_cache_spec(".k", k, n=2, kv_shards=True)
    assert spec == P(None, None, None, "model", None)
    assert sharding_rules.serve_tp_cache_spec(
        ".k", k, n=2, kv_shards=False) == P()
    assert sharding_rules.serve_tp_cache_spec(
        ".length", jnp.zeros((1, 2), jnp.int32), n=2, kv_shards=True) == P()
    with pytest.raises(ValueError, match="does not divide"):
        sharding_rules.serve_tp_cache_spec(
            ".v", jnp.zeros((1, 2, 8, 3, 16)), n=2, kv_shards=True)


def test_tpconfig_gathers_only_o_and_down():
    tp = tp_serve.TPConfig(n=2)
    assert tp.gathers("layers.pos0.attn.o_proj")
    assert tp.gathers("layers.pos1.mlp.down_proj")
    assert not tp.gathers("layers.pos0.attn.q_proj")
    assert not tp.gathers("layers.pos0.mlp.up_proj")
    assert not tp.gathers("layers.pos0.moe.down_proj")   # MoE is replicated
    assert not tp.gathers("lm_head")


def test_engine_rejects_mesh_without_model_axis():
    from repro.configs import reduced_config
    from repro.core.policy import uniform_schedule
    from repro.models.layers import Runtime
    from repro.models.transformer import LM
    from repro.serve import ServeEngine
    cfg = reduced_config("qwen3-8b")
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = uniform_schedule({"8/8": (8, 8)})
    rt = Runtime(policy=sched.policy_for(), mode="serve", schedule=sched)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape((1,)), ("data",))
    with pytest.raises(ValueError, match="'model' axis"):
        ServeEngine(model, params, rt, max_batch=2, max_len=32, mesh=mesh)


# -------------------------------------------------- wire-cost accounting
def test_decode_wire_stats_ratios():
    from repro.configs import reduced_config
    cfg = reduced_config("qwen3-8b")       # attn+mlp every layer
    tp = tp_serve.TPConfig(n=2)
    s8 = tp_serve.decode_wire_stats(cfg, tp, ((4, 8),))
    assert s8["bytes_per_element"] == 1.0
    assert s8["vs_f32"] == 4.0
    s4 = tp_serve.decode_wire_stats(cfg, tp, ((4, 4),))
    assert s4["bytes_per_element"] == 0.5
    assert s4["vs_f32"] == 8.0
    s2 = tp_serve.decode_wire_stats(cfg, tp, ((4, 2),))
    assert s2["vs_f32"] == 16.0
    mixed = tp_serve.decode_wire_stats(cfg, tp, ((2, 8), (1, 4), (1, 2)))
    assert s4["vs_f32"] > mixed["vs_f32"] > s8["vs_f32"]
    # Ring term: each device sends its 1/n shard to n-1 peers.
    tp4 = tp_serve.TPConfig(n=4)
    s8_4 = tp_serve.decode_wire_stats(cfg, tp4, ((4, 8),))
    assert s8_4["quant_gather_bytes"] / s8["quant_gather_bytes"] \
        == pytest.approx((3 / 4) / (1 / 2))


# -------------------------------------------------- fake-device end-to-end
def test_sharded_engine_token_identity_with_migration():
    """2-device mesh engine == unsharded engine, token for token, across
    mixed 8/4/2 batches, per-slot KV precisions, and a mid-stream
    ``set_tier`` KV migration; KV heads genuinely shard."""
    out = run_subprocess("""
        import dataclasses
        import jax, numpy as np
        from repro.configs import reduced_config
        from repro.core.policy import uniform_schedule
        from repro.launch.mesh import make_serve_mesh
        from repro.models.layers import Runtime
        from repro.models.transformer import LM
        from repro.serve import Request, ServeEngine
        from repro.serve.handle import RequestStatus

        cfg = dataclasses.replace(reduced_config("qwen3-8b"),
                                  num_kv_heads=4)
        model = LM(cfg)
        params = model.init(jax.random.PRNGKey(0))
        sched = uniform_schedule(
            {"8/8": (8, 8), "4/4": (4, 4), "2/2": (2, 2)},
            kv_tiers={"8/8": None, "4/4": 8, "2/2": 4})
        rt = Runtime(policy=sched.policy_for(), mode="serve",
                     schedule=sched)
        tiers = ["8/8", "4/4", "2/2"]

        def serve(mesh):
            rng = np.random.default_rng(0)
            eng = ServeEngine(model, params, rt, max_batch=4, max_len=64,
                              decode_chunk=4, mesh=mesh)
            reqs = [Request(uid=i,
                            prompt=rng.integers(0, cfg.vocab_size, size=4),
                            max_new_tokens=10, tier=tiers[i % 3])
                    for i in range(5)]
            handles = [eng.submit(r) for r in reqs]
            migrated = False
            while eng.has_work:
                eng.step()
                if not migrated:
                    for h in handles:
                        if (h.status is RequestStatus.RUNNING
                                and len(h.tokens) >= 2):
                            h.set_tier("2/2" if h.tier != "2/2"
                                       else "8/8")
                            migrated = True
                            break
            assert migrated
            return {h.uid: h.tokens for h in handles}, eng

        ref, _ = serve(None)
        tp2, eng2 = serve(make_serve_mesh(2))
        assert eng2._tp is not None and eng2._tp.kv_shards
        assert eng2.stats.kv_migrations == 1
        assert ref == tp2, (ref, tp2)
        print("TP_SERVE_OK", sum(len(v) for v in ref.values()))
    """)
    assert "TP_SERVE_OK" in out


def test_sharded_decode_hlo_gathers_are_quantized():
    """The compiled mixed-tier sharded decode must all-gather int8 codes
    (8-bit rows) and bit-packed uint8 bytes (4/2-bit rows).  Activations
    never ride the wire in float: the only float gathers allowed are the
    OUTPUT-column gathers (which keep the residual dtype — f32 on the CPU
    reference model — to preserve bit-identity), identified by the
    ``tp_output_gather`` scope tp_serve puts around them."""
    out = run_subprocess("""
        import dataclasses, re
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import reduced_config
        from repro.core.policy import uniform_schedule
        from repro.launch.mesh import make_serve_mesh
        from repro.models.layers import Runtime
        from repro.models.transformer import LM
        from repro.serve import ServeEngine

        cfg = dataclasses.replace(reduced_config("qwen3-8b"),
                                  num_kv_heads=4)
        model = LM(cfg)
        params = model.init(jax.random.PRNGKey(0))
        sched = uniform_schedule(
            {"8/8": (8, 8), "4/4": (4, 4), "2/2": (2, 2)})
        rt = Runtime(policy=sched.policy_for(), mode="serve",
                     schedule=sched)
        eng = ServeEngine(model, params, rt, max_batch=4, max_len=64,
                          decode_chunk=4, mesh=make_serve_mesh(2))
        groups = (("8/8", 2), ("4/4", 1), ("2/2", 1))
        perm = jnp.arange(4, dtype=jnp.int32)
        txt = eng._decode_chunk.lower(
            eng.params, eng.arena.caches, jnp.zeros((4,), jnp.int32),
            jnp.ones((4,), jnp.int32), perm, n_steps=1, tier=None,
            groups=groups).compile().as_text()
        ags = [l for l in txt.splitlines() if "all-gather(" in l]
        assert any(re.search(r"= s8\\[[0-9,]+\\]\\S* all-gather\\(", l)
                   for l in ags), ags      # int8 wire (8-bit rows)
        assert any(re.search(r"= u8\\[[0-9,]+\\]\\S* all-gather\\(", l)
                   for l in ags), ags      # bit-packed wire (4/2-bit rows)
        # Output-column gathers (the residual dtype) are the only float
        # gathers allowed; tp_serve names them with a scope that lands in
        # the op_name metadata.
        floats = [l for l in ags if re.search(r"= (f32|bf16|f16)\\[", l)]
        assert floats, ags
        for l in floats:
            assert re.search(r'op_name="[^"]*tp_output_gather', l), l
        print("TP_HLO_OK", len(ags))
    """)
    assert "TP_HLO_OK" in out
