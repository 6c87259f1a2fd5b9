"""The decode-attention kernel (``kernels.kv_attention``) under every
caller of ``layers.decode_attention``, driven through engines on the CPU
with the kernel forced on (interpreted), as the TPU path takes it:

* a mixed-KV-arena engine equals homogeneous engines at 8/8 (bf16 KV)
  and 2/2 (int4 KV), on granite-3-8b and qwen3-8b (``qk_norm``);
* the speculative verify replay keeps greedy streams identical to plain
  decoding, on granite-3-8b and the hybrid config;
* a 2-device mesh engine runs the kernel on each device's heads (KV heads
  sharded or the MQA head replicated) and equals the unsharded engine.
"""
import os
import sys

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core.policy import uniform_schedule
from repro.kernels import kv_attention as kva
from repro.models import layers
from repro.models.layers import Runtime
from repro.models.transformer import LM
from repro.serve import Request, ServeEngine, SpecConfig
from test_sharded_serving import run_subprocess

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import chip_smoke  # noqa: E402


@pytest.fixture
def kernel_calls(monkeypatch):
    """``decode_attention`` takes the kernel (interpreted) as on the TPU,
    whatever the shapes; yields the (q, k) shapes of every call traced."""
    monkeypatch.setattr(layers, "decode_kernel_engages", lambda *a: True)
    calls = []
    kernel = kva.kv_decode_attention

    def spy(q, k, *args, **kw):
        calls.append((q.shape, k.shape))
        return kernel(q, k, *args, **kw)

    monkeypatch.setattr(kva, "kv_decode_attention", spy)
    return calls


@pytest.mark.parametrize("arch", ["granite-3-8b", "qwen3-8b"])
def test_mixed_arena_equals_homogeneous_through_the_kernel(arch,
                                                           kernel_calls):
    """The chip's ``--kv-identity`` check with the kernel as the decode
    attention: a mixed-KV-arena engine and homogeneous engines at 8/8
    (bf16 KV) and 2/2 (int4 KV) emit identical tokens (qwen3's qk_norm
    acts before the cache)."""
    smoke = chip_smoke.Smoke(reduced_config(arch),
                             prompt_lens=(12, 3, 7, 9), max_new=9,
                             max_len=128, prompt_bucket=16)
    chip_smoke.run_kv_identity(smoke)
    assert kernel_calls


@pytest.mark.parametrize("arch", ["granite-3-8b", "jamba-1.5-large-398b"])
def test_speculative_verify_through_the_kernel(arch, kernel_calls):
    """The verify window replays append + decode attention per position:
    through the kernel, a greedy speculative stream still equals plain
    decoding at its verify tier (mixed KV arena; the hybrid config's
    attention layers too)."""
    cfg = reduced_config(arch)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = uniform_schedule({"8/8": (8, 8), "4/4": (4, 4)},
                             kv_tiers={"8/8": None, "4/4": 4})
    rt = Runtime(policy=sched.policy_for(), mode="serve", schedule=sched)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=4 + i) for i in range(2)]

    def run(spec):
        eng = ServeEngine(model, params, rt, max_batch=2, max_len=128,
                          decode_chunk=2)
        return eng.run([Request(uid=i, prompt=p, max_new_tokens=6,
                                tier="8/8", spec=spec)
                        for i, p in enumerate(prompts)]), eng.stats

    base, _ = run(None)
    spec, st = run(SpecConfig(draft_tier="4/4", k=2))
    assert st.spec_rounds > 0 and spec == base
    assert kernel_calls


def test_mesh_engine_through_the_kernel():
    """Inside a 2-device mesh engine's shard_map the kernel runs on the
    local heads — KV heads sharded (8/4 -> 4/2 a device) or the one MQA
    head replicated (4/1 -> 2/1) — and the tokens equal the unsharded
    engine's."""
    out = run_subprocess("""
        import dataclasses
        import jax, numpy as np
        from repro.configs import reduced_config
        from repro.core.policy import uniform_schedule
        from repro.kernels import kv_attention as kva
        from repro.launch.mesh import make_serve_mesh
        from repro.models import layers
        from repro.models.layers import Runtime
        from repro.models.transformer import LM
        from repro.serve import Request, ServeEngine

        layers.decode_kernel_engages = lambda *a: True
        calls = []
        kernel = kva.kv_decode_attention

        def spy(q, k, *args, **kw):
            calls.append((q.shape[1], k.shape[2]))
            return kernel(q, k, *args, **kw)

        kva.kv_decode_attention = spy
        sched = uniform_schedule(
            {"8/8": (8, 8), "4/4": (4, 4), "2/2": (2, 2)},
            kv_tiers={"8/8": None, "4/4": 8, "2/2": 4})
        rt = Runtime(policy=sched.policy_for(), mode="serve",
                     schedule=sched)
        for heads, kv_heads in ((8, 4), (4, 1)):
            cfg = dataclasses.replace(reduced_config("granite-3-8b"),
                                      num_heads=heads, num_kv_heads=kv_heads)
            model = LM(cfg)
            params = model.init(jax.random.PRNGKey(0))

            def serve(mesh):
                rng = np.random.default_rng(0)
                eng = ServeEngine(model, params, rt, max_batch=3,
                                  max_len=128, decode_chunk=4, mesh=mesh)
                return eng.run([
                    Request(uid=i,
                            prompt=rng.integers(0, cfg.vocab_size, size=5),
                            max_new_tokens=8, tier=("8/8", "4/4", "2/2")[i])
                    for i in range(3)])

            del calls[:]
            ref = serve(None)
            assert set(calls) == {(heads, kv_heads)}, calls
            del calls[:]
            tp2 = serve(make_serve_mesh(2))
            local = (heads // 2, kv_heads // 2 if kv_heads > 1 else 1)
            assert set(calls) == {local}, (calls, local)
            assert ref == tp2, (ref, tp2)
            print("TP_KERNEL_OK", heads, kv_heads, local)
    """)
    assert out.count("TP_KERNEL_OK") == 2, out
