"""Distributed substrate: sharding rules, gradient compression, pipeline
parallelism, reduced dry-run.  Multi-device tests run in subprocesses with
XLA_FLAGS-faked CPU devices so the main test session keeps 1 device.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(body: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_param_sharding_rules_cover_all_archs():
    """Every full-config param leaf gets a valid spec on a tiny fake mesh."""
    from repro.configs import ARCHS
    from repro.distributed import sharding_rules as rules
    from repro.models.transformer import LM
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    for arch, cfg in ARCHS.items():
        shapes = jax.eval_shape(LM(cfg).init, jax.random.key(0))
        sh = rules.tree_shardings(mesh, shapes)
        assert len(jax.tree.leaves(sh)) == len(jax.tree.leaves(shapes))


def test_projection_specs_are_2d_sharded():
    from repro.configs import get_config
    from repro.distributed import sharding_rules as rules
    from repro.models.transformer import LM
    import numpy as np
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = get_config("qwen3-8b")
    shapes = jax.eval_shape(LM(cfg).init, jax.random.key(0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    specs = {jax.tree_util.keystr(kp): rules.param_spec(
        mesh, jax.tree_util.keystr(kp), leaf) for kp, leaf in flat}
    qproj = next(v for k, v in specs.items() if "q_proj" in k)
    assert qproj == jax.sharding.PartitionSpec(None, "data", "model")
    emb = next(v for k, v in specs.items() if "emb" in k)
    assert emb == jax.sharding.PartitionSpec("model", "data")


def test_grok_expert_fallback_to_tp():
    """8 experts cannot divide a 16-way model axis -> 2D TP fallback."""
    from repro.distributed import sharding_rules as rules
    import numpy as np
    devs = np.array(jax.devices() * 16)[:16].reshape(1, 16)
    mesh = jax.sharding.Mesh(devs, ("data", "model"))
    leaf = jax.ShapeDtypeStruct((8, 6144, 32768), jnp.bfloat16)
    spec = rules.param_spec(mesh, "['periods']['pos0']['moe']['gate_proj']['w']",
                            leaf)
    assert spec[0] is None          # experts NOT sharded (8 % 16 != 0)


def test_compressed_psum_matches_mean():
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum
        mesh = jax.make_mesh((8,), ("dp",))
        def f(g, e):
            return compressed_psum(g, e, axis_name="dp", bits=8)
        fm = jax.jit(jax.shard_map(f, mesh=mesh,
                                   in_specs=(P("dp"), P("dp")),
                                   out_specs=(P("dp"), P("dp"))))
        rng = np.random.default_rng(0)
        g = rng.normal(size=(8, 16, 32)).astype(np.float32)
        e = np.zeros_like(g)
        mean, err = fm(g, e)
        mean = np.asarray(mean)
        want = g.mean(0, keepdims=True)
        rel = np.abs(mean - want).max() / np.abs(want).max()
        assert rel < 0.05, rel
        # error feedback: err holds the residual
        assert np.abs(np.asarray(err)).max() > 0
        print("COMPRESSION_OK", rel)
    """)
    assert "COMPRESSION_OK" in out


def test_error_feedback_reduces_bias():
    """Averaged over steps, error feedback drives the compression bias ~0."""
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum
        mesh = jax.make_mesh((4,), ("dp",))
        def f(g, e):
            return compressed_psum(g, e, axis_name="dp", bits=8)
        fm = jax.jit(jax.shard_map(f, mesh=mesh,
                                   in_specs=(P("dp"), P("dp")),
                                   out_specs=(P("dp"), P("dp"))))
        rng = np.random.default_rng(1)
        g = rng.normal(size=(4, 8, 8)).astype(np.float32)  # constant grads
        e = np.zeros_like(g)
        acc = 0.0
        n = 20
        for _ in range(n):
            mean, e = fm(g, e)
            acc = acc + np.asarray(mean)
        want = g.mean(0, keepdims=True) * n
        rel = np.abs(acc - want).max() / np.abs(want).max()
        assert rel < 0.01, rel
        print("EF_OK", rel)
    """)
    assert "EF_OK" in out


def test_pipeline_matches_sequential():
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import run_pipeline
        mesh = jax.make_mesh((4,), ("stage",))
        def stage_fn(w, x):
            return jnp.tanh(x @ w)
        rng = np.random.default_rng(0)
        ws = rng.normal(size=(4, 16, 16)).astype(np.float32) * 0.5
        xs = rng.normal(size=(6, 3, 16)).astype(np.float32)  # 6 microbatches
        got = np.asarray(run_pipeline(mesh, stage_fn, jnp.asarray(ws),
                                      jnp.asarray(xs)))
        want = xs
        for s in range(4):
            want = np.tanh(want @ ws[s])
        assert np.allclose(got, want, atol=1e-5), np.abs(got-want).max()
        print("PIPELINE_OK")
    """)
    assert "PIPELINE_OK" in out


def test_reduced_dryrun_end_to_end(tmp_path):
    """The dry-run driver itself (2x2 mesh, tiny config) — lowering,
    compile, memory/cost/collective extraction."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["REPRO_DRYRUN_DEVICES"] = "4"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen3-8b",
         "--shape", "train_4k", "--reduced", "--out", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    res = json.load(open(tmp_path / "qwen3-8b__train_4k__2x2.json"))
    assert not res["skipped"]
    assert res["flops"] > 0
    assert res["collectives"]["total_bytes"] > 0
    assert res["memory"]["temp_size_in_bytes"] > 0


def test_long500k_skip_rule():
    from repro.configs import get_config
    from repro.launch.specs import SHAPES, cell_applicable
    ok, _ = cell_applicable(get_config("qwen3-8b"), SHAPES["long_500k"])
    assert not ok
    ok, _ = cell_applicable(get_config("mamba2-1.3b"), SHAPES["long_500k"])
    assert ok
    ok, _ = cell_applicable(get_config("jamba-1.5-large-398b"),
                            SHAPES["long_500k"])
    assert ok


def test_make_production_mesh_shapes():
    """Mesh factory contract (validated on fake devices in a subprocess)."""
    out = run_subprocess("""
        from repro.launch.mesh import make_production_mesh
        m = make_production_mesh()
        assert m.devices.shape == (16, 16) and m.axis_names == ("data", "model")
        m2 = make_production_mesh(multi_pod=True)
        assert m2.devices.shape == (2, 16, 16)
        assert m2.axis_names == ("pod", "data", "model")
        print("MESH_OK")
    """, devices=512)
    assert "MESH_OK" in out


def test_elastic_resume_across_device_counts(tmp_path):
    """Checkpoint written under a 4-device mesh restores onto 8 devices —
    the elastic re-shard contract."""
    body_save = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import checkpoint as ckpt
        mesh = jax.make_mesh((len(jax.devices()),), ("data",))
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        xs = jax.device_put(x, NamedSharding(mesh, P("data")))
        ckpt.save(r"CKPT_DIR", 1, {"w": xs}, extra={"mesh": len(jax.devices())})
        print("SAVED", len(jax.devices()))
    """.replace("CKPT_DIR", str(tmp_path))
    body_load = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import checkpoint as ckpt
        mesh = jax.make_mesh((len(jax.devices()),), ("data",))
        target = {"w": jnp.zeros((8, 8), jnp.float32)}
        def shard_fn(path, arr):
            return NamedSharding(mesh, P("data"))
        restored, extra = ckpt.restore(r"CKPT_DIR", 1, target,
                                       sharding_fn=shard_fn)
        w = restored["w"]
        assert len(w.sharding.device_set) == len(jax.devices())
        np.testing.assert_array_equal(
            np.asarray(w), np.arange(64, dtype=np.float32).reshape(8, 8))
        print("RESTORED", len(jax.devices()), "from", extra["mesh"])
    """.replace("CKPT_DIR", str(tmp_path))
    out = run_subprocess(body_save, devices=4)
    assert "SAVED 4" in out
    out = run_subprocess(body_load, devices=8)
    assert "RESTORED 8 from 4" in out


def test_reduced_dryrun_decode_cell(tmp_path):
    """Decode-kind cell through the dry-run driver (prepared quantized
    weights + KV caches + serve_step lowering)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["REPRO_DRYRUN_DEVICES"] = "4"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "mamba2-1.3b",
         "--shape", "long_500k", "--reduced", "--kv-bits", "8",
         "--out", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    res = json.load(open(tmp_path / "mamba2-1.3b__long_500k__2x2.json"))
    assert not res["skipped"]
    assert res["kind"] == "decode"
    assert res["flops"] > 0
