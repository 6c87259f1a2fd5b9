"""chip_smoke.py off the chip: it refuses to run without a TPU, its phases
run end to end on the CPU at smoke width (Pallas in interpret mode), and
the entry points place the compile cache where they should."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.configs import reduced_config  # noqa: E402
from repro.launch import compile_cache  # noqa: E402


def test_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out


def test_fails_outside_the_repo(tmp_path):
    """Alone in a directory (no ``src/``) the script fails and prints no
    result, whatever the platform."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_one_chip_phases_on_cpu(capsys):
    """Serve (pallas), reference (decomposed, same store: identical tokens
    and logits) and packed (identical tokens), at smoke width."""
    smoke = chip_smoke.Smoke(reduced_config("granite-3-8b"),
                             prompt_lens=(12, 3, 7, 9, 5, 11, 4, 8),
                             max_new=6, max_len=32, prompt_bucket=16)
    chip_smoke.run_one_chip(smoke, expect_kernels=False)
    out = capsys.readouterr().out
    assert "reference: token streams identical" in out
    assert "prefill last-position logits identical" in out
    assert "packed: token streams identical" in out


def test_kv_identity_phase_on_cpu(capsys):
    """A mixed-KV-arena engine and homogeneous-KV engines at 8/8 (bf16)
    and 2/2 (int4) emit identical token streams at those tiers."""
    smoke = chip_smoke.Smoke(reduced_config("granite-3-8b"),
                             prompt_lens=(12, 3, 7, 9, 5, 11), max_new=9,
                             max_len=32, prompt_bucket=16)
    chip_smoke.run_kv_identity(smoke)
    out = capsys.readouterr().out
    assert "kv 8/8 (KV bf16): token streams identical (18 tokens)" in out
    assert "kv 2/2 (KV 4): token streams identical (18 tokens)" in out


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", keyed)


def test_compile_cache_follows_env(monkeypatch, cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.use_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_compile_cache_defaults_to_repo(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_compilation_cache_include_metadata_in_key
