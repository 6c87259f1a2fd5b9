"""A cell at smoke size for the CPU tests: the same harness, traffic
generator, reference and check, on a two-layer model of width 64."""
from __future__ import annotations

import copy
import dataclasses
import json
import os
from typing import Any, Dict

from perfbench import harness

CONFIG: Dict[str, Any] = {
    "repro_arch": "granite-3-8b", "reference": "dense_gqa",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 1, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 512, "padded_vocab": 512, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "rms_norm_eps": 1e-6, "qk_norm": False,
    "dtype": "bfloat16",
    "tiers": {"8/8": {"w_bits": 8, "a_bits": 8, "kv_bits": 16},
              "2/2": {"w_bits": 2, "a_bits": 2, "kv_bits": 4}},
}
MIX: Dict[str, Any] = {
    "tiers": {"8/8": 0.5, "2/2": 0.5},
    "prompt_tokens": {"median": 20, "sigma": 0.5, "min": 8, "max": 40},
    "output_tokens": {"median": 12, "sigma": 0.3, "min": 9, "max": 17},
    "engine": {"max_batch": 2, "max_len": 64, "prompt_bucket": 16,
               "decode_chunk": 4},
    "block": 4,
    "lead_in_s": 0.5,
}
SPEC: Dict[str, Any] = {
    "rate_per_s": 4.0,
    "check": {"served_tokens": 24, "packed_tokens": 1024,
              "limits": {"gap": 0.05}},
}


def arch_config(cfg: Dict[str, Any]) -> Any:
    from repro.configs import reduced_config
    return dataclasses.replace(
        reduced_config(cfg["repro_arch"]), num_layers=cfg["num_hidden_layers"],
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        qk_norm=cfg["qk_norm"])


def cell(**changes: Any) -> harness.Cell:
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = copy.deepcopy(CONFIG)
    config.update(changes)
    return harness.Cell(name="tiny", chips=1, config=config,
                        mix=copy.deepcopy(MIX), spec=copy.deepcopy(SPEC),
                        bench=bench)
