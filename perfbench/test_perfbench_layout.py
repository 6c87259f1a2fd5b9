"""BENCHMARK.json keeps to its contract, every name it holds finds its
file, and every configuration file states the program's published widths
and lists its cuts."""
import dataclasses
import json
import os
import re

import pytest

from perfbench import harness, traffic

ROOT = harness.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    assert BENCH["paths"] == ["perfbench"]
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_configs_and_cells_find_their_files():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _text(c["why"]) and _text(c["source"])
        assert c["file"].startswith("perfbench/") and c["file"].endswith(".json")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert cfg["source"] == c["source"]
        assert all(NAME.match(k) for k in c["reduced"])
    used = set()
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _text(w["why"])
        assert w["config"] in names
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        cell = harness.load_cell(w["name"])
        assert cell.spec["rate_per_s"] > 0
        assert set(cell.mix["tiers"]) <= set(cell.config["tiers"])
    assert used == set(names)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)


def test_metrics():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert _text(m["layer"])
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           m["name"] + ".py"))
        layers.setdefault(m["name"], m["layer"])
    names = [m["name"] for m in BENCH["per_layer"] + BENCH["end_to_end"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cfg_file", sorted(
    f for f in os.listdir(os.path.join(harness.HERE, "configs"))))
def test_config_matches_the_program(cfg_file):
    from repro.configs import get_config
    with open(os.path.join(harness.HERE, "configs", cfg_file)) as f:
        cfg = json.load(f)
    base = get_config(cfg["repro_arch"])
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["vocab_size"], bool(cfg["qk_norm"])) == \
        (base.d_model, base.d_ff, base.num_heads, base.num_kv_heads,
         base.head_dim, base.vocab_size, base.qk_norm)
    arch = harness.arch_config(cfg)
    assert arch.padded_vocab == cfg["padded_vocab"]
    assert arch.num_layers == cfg["num_hidden_layers"] < base.num_layers
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    widths = {"hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "vocab_size"}
    assert not widths & set(cfg["reduced"])
    assert dataclasses.replace(base, num_layers=arch.num_layers,
                               rope_theta=arch.rope_theta,
                               tie_embeddings=arch.tie_embeddings) == arch


def test_layouts_cover_the_engine_key_space():
    two = harness.layouts(["8/8", "2/2"], 16)
    assert len(two) == 17 and (("8/8", 16),) in two and (("2/2", 16),) in two
    assert (("8/8", 5), ("2/2", 11)) in two
    assert harness.layouts(["8/8"], 8) == [(("8/8", 8),)]


def test_mixes_fit_their_engines():
    for name in os.listdir(os.path.join(harness.HERE, "traffic")):
        with open(os.path.join(harness.HERE, "traffic", name)) as f:
            mix = json.load(f)
        eng = mix["engine"]
        assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
            <= eng["max_len"]
        assert abs(sum(mix["tiers"].values()) - 1.0) < 1e-9
        assert max(traffic.prefill_buckets(mix)) <= eng["max_len"]


def test_departures_are_what_the_reference_serves():
    from perfbench.references import dense_gqa
    with open(os.path.join(harness.HERE, "configs", "granite-3-8b.json")) as f:
        cfg = json.load(f)
    assert cfg["rms_norm_eps"] == 1e-5 and cfg["logits_scaling"] == 16
    served = dense_gqa.as_run(cfg)
    assert served["rms_norm_eps"] == 1e-6 and served["logits_scaling"] == 1
    published = {k: v for k, v in cfg.items() if k != "departures"}
    with pytest.raises(ValueError):
        dense_gqa.as_run(published)
