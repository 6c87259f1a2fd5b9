"""A cell at smoke size for the CPU tests: the same harness, traffic
generator, reference and check, on a two-layer model of width 64; a hybrid
cell at smoke size with no reference; and a hybrid configuration file at
published widths for the checks that need no weights."""
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


# jamba's smoke preset with an expert layer in every position: one period
# of seven Mamba-2 layers and one attention layer, 4 experts top-2 and a
# shared expert of the same width.  It names no reference.
HYBRID: Dict[str, Any] = {
    "repro_arch": "jamba-1.5-large-398b",
    "program": {"moe_every": 1, "shared_expert": True},
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 1, "head_dim": 16, "num_hidden_layers": 8,
    "layer_types": ["mamba"] * 7 + ["attention"],
    "mamba_d_state": 16, "mamba_d_head": 16, "mamba_n_heads": 8,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "num_local_experts": 4, "num_experts_per_tok": 2,
    "shared_intermediate_size": 128,
    "vocab_size": 512, "padded_vocab": 512, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "qk_norm": False, "dtype": "bfloat16",
    "tiers": CONFIG["tiers"],
}


def granite4_h_small() -> Dict[str, Any]:
    """granite-4.0-h-small's published keys as a configuration file states
    them, cut to one period of its layer pattern (10 of 40 layers) and to 18
    of 72 experts (one chip's share of four), through jamba's registry entry
    with the ``program`` fields and the departures that entry can take:
    attention last in the period, RoPE, its eps and no muP multipliers.
    Today's program cannot serve it as stated: its expert layer routes over
    the experts it holds (18, not the published 72) and sizes its shared
    expert at the routed width (768, not 1536), so ``harness.arch_config``
    refuses it at those leaves until the program has an experts-held layer
    and a shared expert of its own width."""
    types = ["attention" if i % 10 == 5 else "mamba" for i in range(40)]
    run_types = ["attention" if i % 10 == 9 else "mamba" for i in range(40)]
    return {
        "source": "https://huggingface.co/ibm-granite/granite-4.0-h-small/"
                  "blob/main/config.json",
        "repro_arch": "jamba-1.5-large-398b",
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 768, "layer_types": types,
        "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
        "mamba_proj_bias": False, "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_hidden_layers": 10,
        "num_key_value_heads": 8, "num_local_experts": 18,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True,
        "vocab_size": 100352,
        "head_dim": 128, "padded_vocab": 100352, "qk_norm": False,
        "dtype": "bfloat16", "tiers": copy.deepcopy(CONFIG["tiers"]),
        "program": {"moe_every": 1, "shared_expert": True, "attn_every": 10},
        "reduced": {
            "num_hidden_layers": "40 -> 10: one period of the layer pattern "
                                 "(9 Mamba-2, 1 attention)",
            "num_local_experts": "72 -> 18: experts split four ways over a "
                                 "v5e-4 host, this chip's share"},
        "departures": {
            "why": "what jamba's entry runs where granite-4.0-h-small "
                   "differs",
            "run": {"layer_types": run_types,
                    "position_embedding_type": "rope",
                    "rms_norm_eps": 1e-06,
                    "attention_multiplier": 0.08838834764831845,
                    "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
                    "logits_scaling": 1.0},
            "program": {
                "moe_every": "every layer has experts (jamba: every other)",
                "shared_expert": "a shared expert beside the routed ones",
                "attn_every": "one attention layer in ten (jamba: in eight)"}},
    }


def arch_config(cfg: Dict[str, Any]) -> Any:
    from repro.configs import reduced_config
    return dataclasses.replace(
        reduced_config(cfg["repro_arch"]), num_layers=cfg["num_hidden_layers"],
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        qk_norm=cfg["qk_norm"], **cfg.get("program", {}))


def cell(config: Dict[str, Any] = CONFIG, **changes: Any) -> harness.Cell:
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = copy.deepcopy(config)
    config.update(changes)
    return harness.Cell(name="tiny", chips=1, config=config,
                        mix=copy.deepcopy(MIX), spec=copy.deepcopy(SPEC),
                        bench=bench)
