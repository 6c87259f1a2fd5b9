#!/usr/bin/env python3
"""Compile a configuration's serving programs for a described TPU v5e, with
no chip attached, and print their ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse.py granite-3-8b

It lowers the model's prefill (one prompt bucket, one tier) and one
mixed-tier decode step over the full slot batch, at the configuration's
published widths and depth, on the packed superplane store, with shapes
only (``jax.eval_shape``): nothing is allocated at full size.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(name: str, mix_name: str = "mixed-decode") -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from perfbench import harness
    from repro.kernels import ops
    from repro.models.layers import Runtime
    from repro.models.transformer import LM
    from repro.serve.engine import prepare_params, serve_jit

    ops._interpret = lambda: False          # compile the kernels for the chip
    ops._on_tpu = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", mix_name + ".json")) as f:
        eng = json.load(f)["engine"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    arch = harness.arch_config(cfg)
    model = LM(arch)
    sched = harness.schedule(cfg)
    rt = Runtime(policy=sched.policy_for(), mode="serve", schedule=sched)
    floats = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    store = jax.eval_shape(lambda p: prepare_params(
        p, sched.prepare_policy(), model, packed=True, superplane=True)[0],
        floats)
    b, s = eng["max_batch"], eng["max_len"]
    caches = jax.eval_shape(lambda: model.init_cache(
        b, s, kv_bits=sched.kv_modes))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    def report(what, compiled):
        m = compiled.memory_analysis()
        print(json.dumps({"config": name, "program": what,
                          "argument_bytes": m.argument_size_in_bytes,
                          "output_bytes": m.output_size_in_bytes,
                          "temp_bytes": m.temp_size_in_bytes,
                          "custom_calls": compiled.as_text().count(
                              "tpu_custom_call")}), flush=True)

    tiers = list(cfg["tiers"])
    groups = ((tiers[0], b // 2), (tiers[1], b - b // 2))
    perm = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=chip)
    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=chip)

    @serve_jit
    def decode(params, caches, tok, perm):
        return model.decode_step(params, rt.for_groups(groups, perm), caches,
                                 tokens=tok, active=jnp.ones((b,), bool))

    report(f"decode step {groups}",
           decode.lower(on_chip(store), on_chip(caches), tok, perm).compile())
    bucket = eng["prompt_bucket"] * 4
    one = jax.eval_shape(lambda: model.init_cache(1, s, kv_bits=sched.kv_modes))
    ptok = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=chip)
    plen = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=chip)

    @serve_jit
    def prefill(params, caches, tokens, lengths):
        return model.prefill(params, rt.for_tier(tiers[0]), caches,
                             tokens=tokens, seq_lengths=lengths)

    report(f"prefill {tiers[0]} x {bucket}",
           prefill.lower(on_chip(store), on_chip(one), ptok, plen).compile())


if __name__ == "__main__":
    main(*sys.argv[1:])
