"""BENCHMARK.json keeps to its contract, every name it holds finds its
file, and every configuration file states the program's published widths
and lists its cuts."""
import dataclasses
import json
import os
import re

import pytest

from perfbench import harness, tiny, traffic, weights

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


def floors(cfg):
    """The guide's floors that the file's cuts fall under: a whole period of
    the published layer pattern and at least four layers after the leading
    dense ones (``first_k_dense_replace``, each held once), at least 8
    experts held, at least an eighth of the vocabulary."""
    lead = cfg.get("first_k_dense_replace", 0)
    depth = weights.published(cfg, "num_hidden_layers")
    kinds = weights.layer_kinds({**cfg, "departures": {},
                                 "num_hidden_layers": depth})[lead:]
    rest = cfg["num_hidden_layers"] - lead
    out = []
    if rest % weights.period(kinds):
        out.append("period")
    if rest < 4:
        out.append("four layers")
    if cfg.get("num_local_experts", 8) < 8:
        out.append("experts")
    if 8 * cfg["vocab_size"] < weights.published(cfg, "vocab_size"):
        out.append("vocabulary")
    return out


def check_cuts(cfg):
    """What the file itself states: ``reduced`` holds only counts held here,
    never a width, each cut stated and above the guide's floors; no width
    or count under ``departures.run``."""
    reduced = set(cfg["reduced"])
    assert "num_hidden_layers" in reduced and reduced <= set(harness.COUNTS)
    assert not any(weights.is_size(k) for k in
                   cfg.get("departures", {}).get("run", {}))
    for key in reduced:
        published, held = weights.cut(cfg, key)
        assert held == cfg[key] < published
    assert floors(cfg) == []


def check_file(cfg):
    """The checks every configuration file passes: ``check_cuts``, and the
    program's config runs every width and count as the file states it, its
    tree in the shapes the file's layer list states."""
    from repro.configs import get_config
    check_cuts(cfg)
    arch = harness.arch_config(cfg)
    for key, field in harness.FIELDS.items():
        if key in cfg:
            assert getattr(arch, field) == cfg[key], key
    assert arch.padded_vocab == cfg["padded_vocab"]
    assert arch.num_layers < get_config(cfg["repro_arch"]).num_layers


@pytest.mark.parametrize("cfg_file", sorted(
    f for f in os.listdir(os.path.join(harness.HERE, "configs"))))
def test_config_matches_the_program(cfg_file):
    with open(os.path.join(harness.HERE, "configs", cfg_file)) as f:
        check_file(json.load(f))


def test_a_hybrid_file_passes_through_another_entry():
    """granite-4.0-h-small's published keys, cut to one period and 18 of 72
    experts, through jamba's registry entry: only data, no harness edit.
    The file passes every check of its own; the program's tree differs from
    it at the router (over the 18 experts held, not the published 72) and
    the shared expert (768 wide, not 1536) alone, so ``arch_config``
    refuses it until the program has an experts-held layer and a shared
    expert of its own width."""
    cfg = tiny.granite4_h_small()
    check_cuts(cfg)
    with pytest.raises(ValueError) as err:
        harness.arch_config(cfg)
    at = set(re.findall(r"periods/pos\d+/(\S+):", str(err.value)))
    assert at == {"moe/router/w", "moe/shared/gate_proj/w",
                  "moe/shared/up_proj/w", "moe/shared/down_proj/w"}
    assert "(1, 4096, 72)" in str(err.value)


def test_program_fields_must_be_named():
    cfg = tiny.granite4_h_small()
    del cfg["departures"]["program"]["moe_every"]
    with pytest.raises(ValueError, match="moe_every"):
        harness.program_config(cfg)
    named = tiny.granite4_h_small()      # num_experts: via reduced
    named["program"]["num_experts"] = 72
    assert harness.program_config(named).num_experts == 72
    assert "num_experts" in harness.named_fields(named)
    unknown = tiny.granite4_h_small()
    unknown["program"]["experts_here"] = 18
    unknown["departures"]["program"]["experts_here"] = "held here"
    with pytest.raises(TypeError, match="experts_here"):
        harness.program_config(unknown)


def test_a_file_without_program_is_as_before():
    from repro.configs import get_config
    with open(os.path.join(harness.HERE, "configs", "granite-3-8b.json")) as f:
        cfg = json.load(f)
    assert harness.arch_config(cfg) == dataclasses.replace(
        get_config("granite-3-8b"), num_layers=16, rope_theta=10000.0,
        tie_embeddings=True)
    wrong = dict(cfg, layer_types=["attention"] * 8 + ["mamba"] * 8)
    with pytest.raises(KeyError):
        harness.arch_config(wrong)      # a Mamba-2 layer states no sizes


def _departed(key, value):
    cfg = tiny.granite4_h_small()
    cfg["departures"]["run"][key] = value
    return cfg


def _program(field, value):
    cfg = tiny.granite4_h_small()
    cfg["program"][field] = value
    cfg["departures"]["program"][field] = "named"
    return cfg


@pytest.mark.parametrize("cfg,match", [
    (_departed("shared_intermediate_size", 768), "departures.run"),
    (_departed("num_local_experts", 18), "departures.run"),
    (_departed("hidden_size", 2048), "departures.run"),
    (_departed("mamba_n_heads", 64), "departures.run"),
    (_program("d_model", 2048), "a width"),
    (_program("experts_per_token", 2), "a width"),
    (dict(tiny.granite4_h_small(), num_local_experts=16), "reduced"),
    (dict(tiny.granite4_h_small(), position_embedding_type="alibi",
          departures={}), "RoPE"),
], ids=["shared-width", "router-count", "hidden", "ssm-heads",
        "program-width", "program-top-k", "held-count", "position"])
def test_hybrid_widths_are_checked(cfg, match):
    with pytest.raises(ValueError, match=match):
        harness.program_config(cfg)


def test_widths_come_from_the_file():
    cfg = tiny.granite4_h_small()
    arch = harness.program_config(cfg)
    assert (arch.d_model, arch.d_ff, arch.ssm_state, arch.ssm_headdim,
            arch.ssm_heads, arch.experts_per_token, arch.num_experts,
            arch.ssm_chunk, arch.num_layers) == \
        (4096, 768, 128, 64, 128, 10, 18, 256, 10)
    with pytest.raises(ValueError) as err:      # Mamba-2 runs d_state 64
        harness.arch_config(dict(cfg, shared_intermediate_size=768,
                                 mamba_d_state=64))
    assert "router" in str(err.value) and "mamba" not in str(err.value)


def test_cut_and_published():
    cfg = tiny.granite4_h_small()
    assert weights.cut(cfg, "num_local_experts") == (72, 18)
    assert weights.published(cfg, "num_local_experts") == 72
    assert weights.published(cfg, "hidden_size") == 4096
    cfg["reduced"]["num_hidden_layers"] = "one period"
    with pytest.raises(ValueError, match="published"):
        weights.cut(cfg, "num_hidden_layers")


def _granite(**changes):
    with open(os.path.join(harness.HERE, "configs", "granite-3-8b.json")) as f:
        cfg = json.load(f)
    reduced = dict(cfg["reduced"])
    for key, (published, held) in changes.items():
        if key == "first_k_dense_replace":
            cfg[key] = held
            continue
        reduced[key] = f"{published} -> {held}: held here"
        cfg[key] = held
    cfg["reduced"] = reduced
    return cfg


def _hybrid(**changes):
    cfg = tiny.granite4_h_small()
    cfg.update(changes)
    return cfg


@pytest.mark.parametrize("cfg,broken", [
    (_granite(), []),
    (tiny.granite4_h_small(), []),
    (_granite(num_hidden_layers=(40, 3)), ["four layers"]),
    (_granite(num_hidden_layers=(40, 4)), []),
    (_granite(first_k_dense_replace=(0, 1),
              num_hidden_layers=(40, 4)), ["four layers"]),
    (_granite(first_k_dense_replace=(0, 1),
              num_hidden_layers=(40, 5)), []),
    (_granite(first_k_dense_replace=(0, 2),
              num_hidden_layers=(40, 1)), ["four layers"]),
    (_hybrid(num_hidden_layers=5), ["period"]),
    (_hybrid(num_hidden_layers=20), []),
    (_hybrid(num_local_experts=4), ["experts"]),
    (_granite(vocab_size=(49155, 6000)), ["vocabulary"]),
    (_granite(vocab_size=(49155, 6200)), []),
], ids=["granite", "granite4", "three-layers", "four-layers",
        "dense-lead-three", "dense-lead-four", "dense-lead-only", "half-period",
        "two-periods", "four-experts", "vocab-under-eighth", "vocab-eighth"])
def test_floors(cfg, broken):
    assert floors(cfg) == broken


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
