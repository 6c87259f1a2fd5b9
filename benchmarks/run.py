"""Benchmark harness: one function per paper table/figure + kernel
micro-benchmarks + dry-run roofline summary.

Prints ``name,us_per_call,derived`` CSV rows.  ``us_per_call`` is the wall
time of the underlying model/kernel evaluation on this host (CPU; TPU is the
target, so derived analytic quantities — the actual reproduction targets —
are in ``derived``).
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, iters=3):
    fn()  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e6


# Rows accumulated for the --json artifact (BENCH_<pr>.json in CI).
_RESULTS: list = []


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}")
    _RESULTS.append({"name": name, "us_per_call": round(us, 1),
                     "derived": derived})


def bench_table2_csa_vs_bat():
    """Table II: CSA split tree vs binary adder tree (area / power)."""
    from repro.core.adder_tree import csa_tree_sum
    from repro.hwmodel.adder_tree_cost import PAPER_TABLE2, table2_model
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.integers(-4, 4, size=(64, 64)), jnp.int32)
    f = jax.jit(lambda x: csa_tree_sum(x, axis=-1))
    us = _timeit(lambda: jax.block_until_ready(f(p)))
    m = table2_model()
    _row("table2_csa_vs_bat", us,
         f"area={m['area']:.4f}(paper {PAPER_TABLE2['area']}) "
         f"P_unsigned={m['power_unsigned']:.4f}(paper {PAPER_TABLE2['power_unsigned']}) "
         f"P_signed={m['power_signed']:.4f}(paper {PAPER_TABLE2['power_signed']})")


def bench_table3_comparison():
    """Table III: throughput + energy efficiency vs published accelerators."""
    from repro.core.pe_array import pe_array_matmul
    from repro.hwmodel import energy
    rng = np.random.default_rng(1)
    w = rng.integers(-2, 2, size=(64, 64))
    a = rng.integers(-2, 2, size=(8, 64))
    us = _timeit(lambda: jax.block_until_ready(
        pe_array_matmul(a, w, w_bits=2, a_bits=2)[0]))
    t3 = energy.table3_ours()
    imp = energy.improvement_vs_bitsystolic()
    _row("table3_comparison", us,
         f"peak={t3['peak_tops']:.2f}TOPS(paper 4.09) "
         f"eff8={t3['eff_8bit']:.2f} eff4={t3['eff_4bit']:.2f} "
         f"eff2={t3['eff_2bit']:.2f}TOPS/W "
         f"vsBitSystolic=+{imp['8bit']:.1%}/+{imp['4bit']:.1%}/+{imp['2bit']:.1%}"
         f"(paper +18.7%/+10.5%/+11.2%)")


def bench_fig7_breakdown():
    """Fig 7: PE-array area/power breakdown; Fig-4 path = 0.97 % area."""
    from repro.hwmodel import breakdown
    t0 = time.perf_counter()
    af = breakdown.area_fractions()
    pf = breakdown.power_breakdown()
    us = (time.perf_counter() - t0) * 1e6
    top_a = max(af, key=af.get)
    _row("fig7_breakdown", us,
         f"indep_path_area={breakdown.indep_path_fraction():.4f}(paper 0.0097) "
         f"largest_area={top_a}:{af[top_a]:.2f} "
         f"tree_power={pf['adder_trees']:.2f}")


def bench_fig8_energy_efficiency():
    """Fig 8: PE-array energy efficiency vs input toggle rate, per precision."""
    from repro.hwmodel import energy
    t0 = time.perf_counter()
    rows = []
    for bits in (8, 4, 3, 2):
        c = energy.fig8_curve(bits, bits, toggles=(0.1, 0.3, 0.5, 0.7, 0.9))
        rows.append(f"{bits}b@0.5={c[0.5]:.1f}")
    us = (time.perf_counter() - t0) * 1e6
    _row("fig8_energy_efficiency", us,
         " ".join(rows) + " (paper 14/52.1/139.8/205.8 @ toggle 0.5)")


def bench_mobilenetv2_power():
    """§IV: mixed-precision MobileNetV2 power reduction vs fixed 8-bit."""
    from repro.hwmodel import mobilenet
    t0 = time.perf_counter()
    sweep = {b: mobilenet.power_reduction_vs_8bit(b)
             for b in (3.0, 3.25, 3.5, 3.75, 4.0, 5.0, 6.0)}
    us = (time.perf_counter() - t0) * 1e6
    best_b = min(sweep, key=lambda b: abs(sweep[b] - mobilenet.PAPER_REDUCTION))
    _row("mobilenetv2_power", us,
         f"macs={mobilenet.total_macs()/1e6:.0f}M "
         f"reduction@avg{best_b}b={sweep[best_b]:.1%}(paper 35.2%) "
         f"sweep={{" + " ".join(f"{b}:{r:.0%}" for b, r in sweep.items()) + "}")


def bench_mobilenetv2_throughput():
    """§IV inference performance: fps on the 64x64 array (cycle model)."""
    from repro.hwmodel import mobilenet
    t0 = time.perf_counter()
    layers = mobilenet.mobilenet_v2_layers()
    fixed8 = {l.name: 8 for l in layers}
    mixed = mobilenet.allocate_bits(3.75, layers)
    fps8 = mobilenet.inference_fps(fixed8)
    fpsm = mobilenet.inference_fps(mixed)
    us = (time.perf_counter() - t0) * 1e6
    _row("mobilenetv2_throughput", us,
         f"fixed8={fps8:.0f}fps mixed@3.75b={fpsm:.0f}fps "
         f"speedup={fpsm/fps8:.2f}x @500MHz 64x64 array")


def bench_kernel_bitserial_matmul():
    """Flagship Pallas kernel vs oracle (interpret mode) + pass-count law."""
    from repro.core import decompose
    from repro.kernels.bitserial_matmul import bitserial_matmul
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.integers(-128, 128, size=(128, 256)), jnp.int8)
    rows = []
    us_all = 0.0
    for w_bits in (2, 4, 8):
        lo, hi = decompose.weight_range(w_bits, True)
        w = rng.integers(lo, hi + 1, size=(256, 128))
        planes = decompose.decompose_weights(w, w_bits)
        f = lambda: jax.block_until_ready(bitserial_matmul(
            x, planes, w_bits=w_bits, interpret=True))
        us = _timeit(f, iters=2)
        us_all += us
        rows.append(f"{w_bits}b:{decompose.num_planes(w_bits)}pass")
    _row("kernel_bitserial_matmul", us_all / 3,
         "MXU_passes_per_wbits={" + " ".join(rows) + "} (cost ~ w_bits/2)")


def bench_kernel_packed_vs_unpacked():
    """Packed-plane layout: weight bytes/element vs the unpacked layout."""
    from repro.core import decompose
    from repro.kernels import ops
    from repro.kernels.bitserial_matmul import packed_bitserial_matmul
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(-128, 128, size=(128, 256)), jnp.int8)
    w = rng.integers(-8, 8, size=(256, 128))
    planes = decompose.decompose_weights(w, 4)
    packed = ops.pack_planes(planes, 4)
    us = _timeit(lambda: jax.block_until_ready(packed_bitserial_matmul(
        x, packed, w_bits=4, interpret=True)), iters=2)
    _row("kernel_packed_planes", us,
         f"bytes/weight packed={packed.nbytes/w.size:.2f} "
         f"unpacked={np.asarray(planes).nbytes/w.size:.2f} (4-bit)")


def bench_act_quant():
    from repro.kernels.act_quant import act_quant
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(256, 1024)), jnp.float32)
    us = _timeit(lambda: jax.block_until_ready(
        act_quant(x, interpret=True)[0]), iters=2)
    _row("kernel_act_quant", us, "per-row int8 quant, fused single HBM read")


def bench_pe_array_utilization():
    """Array utilization across 2..8-bit (the paper's central claim)."""
    from repro.core.pe_array import PEArrayConfig, array_utilization, peak_tops
    cfg = PEArrayConfig()
    t0 = time.perf_counter()
    utils = {b: array_utilization(cfg, b) for b in range(2, 9)}
    tops = {b: peak_tops(cfg, b, b) for b in (2, 4, 8)}
    us = (time.perf_counter() - t0) * 1e6
    _row("pe_array_utilization", us,
         "util={" + " ".join(f"{b}:{u:.3f}" for b, u in utils.items()) + "} "
         f"tops 2/4/8={tops[2]:.2f}/{tops[4]:.2f}/{tops[8]:.2f}")


def bench_continuous_batching():
    """Mixed-workload serving: continuous batching vs batch-at-a-time.

    Heterogeneous prompt lengths AND decode budgets; asserts token-identical
    per-request outputs and reports the decode-step saving (the utilization
    win of per-slot admission)."""
    from repro.configs import reduced_config
    from repro.core.policy import uniform_policy
    from repro.models.layers import Runtime
    from repro.models.transformer import LM
    from repro.serve.engine import BatchServeEngine, Request, ServeEngine

    cfg = reduced_config("granite-3-8b")
    model = LM(cfg)
    rng = np.random.default_rng(7)
    params = model.init(jax.random.PRNGKey(0))
    policy = uniform_policy(4, 8, backend="decomposed")
    rt = Runtime(policy=policy, mode="serve", moe_dropless=True)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=3 + i % 7),
                    max_new_tokens=(2, 20, 3, 4)[i % 4])
            for i in range(10)]

    cont = ServeEngine(model, params, rt, max_batch=4, max_len=64,
                       decode_chunk=4)
    t0 = time.perf_counter()
    got = cont.run(reqs)
    us = (time.perf_counter() - t0) * 1e6

    base = BatchServeEngine(model, cont.params, rt, max_batch=4, max_len=64)
    want = base.run(reqs)
    identical = all(got[r.uid] == want[r.uid] for r in reqs)
    assert identical, "continuous-batching outputs diverged from baseline"
    assert cont.stats.decode_steps < base.stats.decode_steps, (
        cont.stats.decode_steps, base.stats.decode_steps)
    _row("serve_continuous_batching", us,
         f"decode_steps cont={cont.stats.decode_steps} "
         f"batch={base.stats.decode_steps} "
         f"slot_steps cont={cont.stats.decode_slot_steps} "
         f"batch={base.stats.decode_slot_steps} "
         f"token_identical={identical}")


def bench_serve_precision_tiers():
    """Runtime-reconfigurable precision serving: ONE engine, one preloaded
    8-bit superplane store, requests decoding at 8/8, 4/4 and 2/2.

    Asserts zero prepare_params calls after construction and per-tier
    token-identity with natively-prepared fixed-precision engines; reports
    tokens/s and decode steps per tier plus the hwmodel's effective TOPS
    (the plane-prefix pass-count law: work scales with the EFFECTIVE bits,
    not the stored ones)."""
    from repro.configs import reduced_config
    from repro.core.policy import uniform_policy, uniform_schedule
    from repro.hwmodel import energy
    from repro.models.layers import Runtime
    from repro.models.transformer import LM
    from repro.serve import engine as engine_mod
    from repro.serve.engine import Request, ServeEngine

    cfg = reduced_config("granite-3-8b")
    model = LM(cfg)
    rng = np.random.default_rng(11)
    params = model.init(jax.random.PRNGKey(0))
    tiers = {"8/8": (8, 8), "4/4": (4, 4), "2/2": (2, 2)}
    sched = uniform_schedule(tiers, backend="decomposed")
    rt = Runtime(policy=sched.policy_for(), mode="serve", moe_dropless=True,
                 schedule=sched)
    names = list(tiers)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=3 + i % 5),
                    max_new_tokens=(3, 6, 4)[i % 3], tier=names[i % 3])
            for i in range(9)]

    eng = ServeEngine(model, params, rt, max_batch=3, max_len=64,
                      decode_chunk=4)
    preps_after_construction = engine_mod.PREPARE_CALLS
    t0 = time.perf_counter()
    got = eng.run(reqs)
    dt = time.perf_counter() - t0
    assert engine_mod.PREPARE_CALLS == preps_after_construction, \
        "weights were re-prepared after construction"

    # Per-tier parity vs engines prepared natively at that precision.
    for tier, (w, a) in tiers.items():
        sub = [r for r in reqs if r.tier == tier]
        native = ServeEngine(
            model, params,
            Runtime(policy=uniform_policy(w, a, backend="decomposed"),
                    mode="serve", moe_dropless=True),
            max_batch=3, max_len=64, decode_chunk=4)
        want = native.run([Request(uid=r.uid, prompt=r.prompt,
                                   max_new_tokens=r.max_new_tokens)
                           for r in sub])
        assert all(got[r.uid] == want[r.uid] for r in sub), tier

    toks = sum(len(v) for v in got.values())
    eff = {t: energy.tier_cost(w, a)["effective_tops"]
           for t, (w, a) in tiers.items()}
    steps = eng.stats.decode_steps_by_tier
    _row("serve_precision_tiers", dt * 1e6 / max(len(reqs), 1),
         f"tokens/s={toks/dt:.1f} preps_after_construction=0 "
         f"tier_switches={eng.stats.tier_switches} "
         "decode_steps={" + " ".join(f"{t}:{steps.get(t, 0)}"
                                     for t in tiers) + "} "
         "eff_TOPS={" + " ".join(f"{t}:{v:.2f}" for t, v in eff.items())
         + "} token_identical_vs_native=True")


def bench_serve_mixed_tiers():
    """Mixed-tier decode batches + per-request KV precision: ONE engine,
    one preloaded superplane store, a mixed 8/4/2 request stream decoding
    TOGETHER in each jitted step (per-row-group plane-prefix GEMMs) with
    per-slot KV tiers (bf16 / int8 / int4-packed in one arena).

    Asserts (the PR's acceptance criteria): zero prepare_params calls after
    construction, per-request token identity with fixed-tier
    BatchServeEngine references, and FEWER total decode steps than
    tier-serialized admission on the same stream."""
    from repro.configs import reduced_config
    from repro.core.policy import uniform_schedule
    from repro.models.layers import Runtime
    from repro.models.transformer import LM
    from repro.serve import engine as engine_mod
    from repro.serve.engine import BatchServeEngine, Request, ServeEngine

    cfg = reduced_config("granite-3-8b")
    model = LM(cfg)
    rng = np.random.default_rng(13)
    params = model.init(jax.random.PRNGKey(0))
    tiers = {"8/8": (8, 8), "4/4": (4, 4), "2/2": (2, 2)}
    sched = uniform_schedule(tiers, backend="decomposed",
                             kv_tiers={"8/8": None, "4/4": 8, "2/2": 4})
    rt = Runtime(policy=sched.policy_for(), mode="serve", moe_dropless=True,
                 schedule=sched)
    names = list(tiers)
    # Per-tier queue depth (2) below max_batch (3): a serialized engine can
    # only fill slots with the ONE tier currently decoding, so every phase
    # runs under-occupied and the phases add up in time, while mixed
    # admission keeps all slots busy with whatever tier waits next — the
    # paper's continuous 2..8-bit scaling under one preloaded weight array.
    budgets = (8, 6, 7, 5, 8, 6)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=3 + i % 5),
                    max_new_tokens=budgets[i], tier=names[i % 3])
            for i in range(6)]

    mixed = ServeEngine(model, params, rt, max_batch=3, max_len=64,
                        decode_chunk=4)
    preps = engine_mod.PREPARE_CALLS
    t0 = time.perf_counter()
    got = mixed.run(reqs)
    dt = time.perf_counter() - t0
    assert engine_mod.PREPARE_CALLS == preps, \
        "weights were re-prepared after construction"

    serial = ServeEngine(model, mixed.params, rt, max_batch=3, max_len=64,
                         decode_chunk=4, mixed_tiers=False)
    got_serial = serial.run([Request(uid=r.uid, prompt=r.prompt,
                                     max_new_tokens=r.max_new_tokens,
                                     tier=r.tier) for r in reqs])

    # Token identity: mixed == serialized == fixed-tier references.
    for tier in tiers:
        sub = [r for r in reqs if r.tier == tier]
        base = BatchServeEngine(model, mixed.params, rt, max_batch=1,
                                max_len=64, tier=tier)
        want = base.run([Request(uid=r.uid, prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens, tier=tier)
                         for r in sub])
        assert all(got[r.uid] == want[r.uid] for r in sub), tier
        assert all(got_serial[r.uid] == want[r.uid] for r in sub), tier
    assert mixed.stats.decode_steps < serial.stats.decode_steps, (
        mixed.stats.decode_steps, serial.stats.decode_steps)

    toks = sum(len(v) for v in got.values())
    _row("serve_mixed_tiers", dt * 1e6 / max(len(reqs), 1),
         f"tokens/s={toks/dt:.1f} "
         f"decode_steps mixed={mixed.stats.decode_steps} "
         f"serialized={serial.stats.decode_steps} "
         f"mixed_chunks={mixed.stats.mixed_tier_chunks} "
         f"preps_after_construction=0 kv_modes={sched.kv_modes} "
         "token_identical_vs_fixed_tier=True")


def bench_serve_observability():
    """Telemetry-on vs telemetry-off serving on the mixed-tier trace.

    The two contracts of ``repro.telemetry`` priced and asserted: the
    telemetry-off engine drains the stream without a single hook call
    (the module-level HOOK_CALLS spy), and the telemetry-on engine stays
    token-identical.
    Derived reports both throughputs plus the TTFT/TPOT p50/p99 the
    registry's histograms estimate without storing samples."""
    from repro.configs import reduced_config
    from repro.core.policy import uniform_schedule
    from repro.models.layers import Runtime
    from repro.models.transformer import LM
    from repro.serve.engine import Request, ServeEngine
    import repro.telemetry as telemetry_mod
    from repro.telemetry import Telemetry

    cfg = reduced_config("granite-3-8b")
    model = LM(cfg)
    rng = np.random.default_rng(13)
    params = model.init(jax.random.PRNGKey(0))
    tiers = {"8/8": (8, 8), "4/4": (4, 4), "2/2": (2, 2)}
    sched = uniform_schedule(tiers, backend="decomposed",
                             kv_tiers={"8/8": None, "4/4": 8, "2/2": 4})
    rt = Runtime(policy=sched.policy_for(), mode="serve", moe_dropless=True,
                 schedule=sched)
    names = list(tiers)
    budgets = (8, 6, 7, 5, 8, 6)
    prompts = [rng.integers(0, cfg.vocab_size, size=3 + i % 5)
               for i in range(6)]

    def requests():
        return [Request(uid=i, prompt=prompts[i], max_new_tokens=budgets[i],
                        tier=names[i % 3]) for i in range(6)]

    off = ServeEngine(model, params, rt, max_batch=3, max_len=64,
                      decode_chunk=4)
    hooks_before = telemetry_mod.HOOK_CALLS
    t0 = time.perf_counter()
    got_off = off.run(requests())
    dt_off = time.perf_counter() - t0
    assert telemetry_mod.HOOK_CALLS == hooks_before, \
        "telemetry-off engine took observability hooks"

    tele = Telemetry()
    on = ServeEngine(model, off.params, rt, max_batch=3, max_len=64,
                     decode_chunk=4, telemetry=tele)
    t0 = time.perf_counter()
    got_on = on.run(requests())
    dt_on = time.perf_counter() - t0
    assert got_on == got_off, "telemetry changed tokens"

    reg = tele.registry
    ttft = reg.get("serve_ttft_ticks")
    tpot = reg.get("serve_tpot_ticks")
    toks = sum(len(v) for v in got_off.values())
    _row("serve_observability", dt_on * 1e6 / max(len(got_on), 1),
         f"tokens/s off={toks/dt_off:.1f} on={toks/dt_on:.1f} "
         f"ttft_ticks p50={ttft.quantile(0.5):.1f} "
         f"p99={ttft.quantile(0.99):.1f} "
         f"tpot_ticks p50={tpot.quantile(0.5):.1f} "
         f"p99={tpot.quantile(0.99):.1f} "
         f"hook_calls=0_when_off token_identical=True")


def bench_fused_decode():
    """One-kernel mixed-tier decode vs the per-group loop it replaced.

    Two engines over the SAME superplane store and mixed 8/4/2 request
    stream: ``fused_decode=True`` (default — rmsnorm-fed activations
    quantized ONCE per input with per-row ranges, one group-switching
    grouped GEMM per projection) vs ``fused_decode=False`` (per-group
    quantize + GEMM + dequant chain).  Asserts token identity (the
    bitwise-stability contract) and — on the pallas backend, counted by
    tracing — that the fused decode step's dispatch count is CONSTANT in
    the number of tier groups and strictly below the per-group path's."""
    from repro.configs import reduced_config
    from repro.core.policy import uniform_schedule
    from repro.models.layers import Runtime
    from repro.models.transformer import LM
    from repro.serve.engine import Request, ServeEngine

    cfg = reduced_config("granite-3-8b")
    model = LM(cfg)
    rng = np.random.default_rng(17)
    params = model.init(jax.random.PRNGKey(0))
    tiers = {"8/8": (8, 8), "4/4": (4, 4), "2/2": (2, 2)}
    kv_tiers = {"8/8": None, "4/4": 8, "2/2": 4}
    sched = uniform_schedule(tiers, backend="decomposed", kv_tiers=kv_tiers)
    rt = Runtime(policy=sched.policy_for(), mode="serve", moe_dropless=True,
                 schedule=sched)
    names = list(tiers)
    budgets = (8, 6, 7, 5, 8, 6)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=3 + i % 5),
                    max_new_tokens=budgets[i], tier=names[i % 3])
            for i in range(6)]

    fused = ServeEngine(model, params, rt, max_batch=3, max_len=64,
                        decode_chunk=4)
    t0 = time.perf_counter()
    got_f = fused.run(reqs)
    dt_f = time.perf_counter() - t0

    pergroup = ServeEngine(model, fused.params, rt, max_batch=3, max_len=64,
                           decode_chunk=4, fused_decode=False)
    t0 = time.perf_counter()
    got_u = pergroup.run([Request(uid=r.uid, prompt=r.prompt,
                                  max_new_tokens=r.max_new_tokens,
                                  tier=r.tier) for r in reqs])
    dt_u = time.perf_counter() - t0
    assert got_f == got_u, "fused decode changed tokens"

    # Dispatches per jitted decode step, pallas backend (trace-only: the
    # jaxpr is counted, nothing executes, so this runs on any host).
    sched_p = uniform_schedule(tiers, backend="pallas", kv_tiers=kv_tiers)
    rt_p = Runtime(policy=sched_p.policy_for(), mode="serve",
                   moe_dropless=True, schedule=sched_p)
    eng_pf = ServeEngine(model, params, rt_p, max_batch=4, max_len=64,
                         decode_chunk=1)
    eng_pu = ServeEngine(model, eng_pf.params, rt_p, max_batch=4, max_len=64,
                         decode_chunk=1, fused_decode=False)
    g2 = (("8/8", 2), ("4/4", 2))
    g3 = (("8/8", 1), ("4/4", 2), ("2/2", 1))
    nf2, nf3 = (eng_pf.decode_dispatch_count(groups=g) for g in (g2, g3))
    nu2, nu3 = (eng_pu.decode_dispatch_count(groups=g) for g in (g2, g3))
    assert nf2 == nf3, "fused dispatch count must not scale with groups"
    assert nf3 < nu3, "fused path must dispatch fewer kernels"

    toks = sum(len(v) for v in got_f.values())
    _row("fused_decode", dt_f * 1e6 / max(len(reqs), 1),
         f"tokens/s fused={toks/dt_f:.1f} per_group={toks/dt_u:.1f} "
         f"dispatches/step 2-tier fused={nf2} per_group={nu2} "
         f"3-tier fused={nf3} per_group={nu3} "
         f"layout_cache={fused.stats.layout_cache_hits}h/"
         f"{fused.stats.layout_cache_misses}m "
         "token_identical=True")


def bench_serve_slo_scheduling():
    """SLO-aware admission vs FIFO on a deadline-skewed mixed-tier trace.

    One engine per policy over the SAME superplane store and arrival
    trace: four long, patient 8/8-4/4 requests arrive first; three short,
    deadline-tight 2/2 requests arrive one clock tick later, behind them
    in the queue.  FIFO admits the patient backlog first, so every urgent
    request waits out a LONG service time; SLOPolicy (deadline slack
    priced by the hwmodel's per-tier cycle cost) admits the urgent ones
    into the first freed slots, delaying each patient request only by a
    SHORT service time.  Asserts (acceptance criteria): token-identity
    between the two policies (admission order never changes a request's
    tokens — the mixed-batch bit-stability contract), strictly better p99
    queue-wait under SLO, zero deadline misses under SLO while FIFO
    misses the urgent ones (the trace is feasible), and zero weight
    re-preparations."""
    from repro.configs import reduced_config
    from repro.core.policy import uniform_schedule
    from repro.models.layers import Runtime
    from repro.models.transformer import LM
    from repro.serve import Request, ServeEngine, SLOPolicy
    from repro.serve import engine as engine_mod

    cfg = reduced_config("granite-3-8b")
    model = LM(cfg)
    rng = np.random.default_rng(17)
    params = model.init(jax.random.PRNGKey(0))
    tiers = {"8/8": (8, 8), "4/4": (4, 4), "2/2": (2, 2)}
    sched = uniform_schedule(tiers, backend="decomposed")
    rt = Runtime(policy=sched.policy_for(), mode="serve", moe_dropless=True,
                 schedule=sched)

    def req(uid, budget, tier, deadline):
        return Request(uid=uid,
                       prompt=rng.integers(0, cfg.vocab_size, size=4 + uid),
                       max_new_tokens=budget, tier=tier, deadline=deadline)

    # (arrival clock, request): long patient head, short urgent tail.
    arrivals = [(0.0, req(0, 16, "8/8", 500.0)),
                (0.0, req(1, 16, "4/4", 500.0)),
                (0.0, req(2, 16, "8/8", 500.0)),
                (0.0, req(3, 16, "4/4", 500.0)),
                (1.0, req(4, 2, "2/2", 18.0)),
                (1.0, req(5, 2, "2/2", 18.0)),
                (1.0, req(6, 2, "2/2", 20.0))]

    store = {}

    def serve(policy):
        eng = ServeEngine(model, store.get("params", params), rt,
                          max_batch=2, max_len=64, decode_chunk=4,
                          scheduler_policy=policy)
        store["params"] = eng.params          # share the superplane store
        preps = engine_mod.PREPARE_CALLS
        pending = list(arrivals)
        t0 = time.perf_counter()
        while pending or eng.has_work:
            while pending and (pending[0][0] <= eng.clock
                               or not eng.has_work):
                eng.submit(pending.pop(0)[1])
            eng.step()
        dt = time.perf_counter() - t0
        assert engine_mod.PREPARE_CALLS == preps, "re-prepared mid-run"
        got = eng.results
        waits = np.array([h.queue_wait for h in eng.handles.values()])
        misses = sum(
            1 for h in eng.handles.values()
            if h.finished_at > h.submitted_at + h.request.deadline)
        toks = sum(len(v) for v in got.values())
        return got, waits, misses, toks, dt

    got_f, waits_f, miss_f, toks, dt_f = serve(None)            # FIFO
    got_s, waits_s, miss_s, _, dt_s = serve(SLOPolicy(sched))
    assert got_s == got_f, "admission order changed a request's tokens"
    p50_f, p99_f = np.percentile(waits_f, [50, 99])
    p50_s, p99_s = np.percentile(waits_s, [50, 99])
    assert p99_s < p99_f, (p99_s, p99_f)
    assert miss_s == 0, f"SLO policy missed {miss_s} feasible deadlines"
    _row("serve_slo_scheduling", (dt_f + dt_s) * 1e6 / 14,
         f"queue_wait_p50 fifo={p50_f:.0f} slo={p50_s:.0f} "
         f"p99 fifo={p99_f:.0f} slo={p99_s:.0f} (decode-step ticks) "
         f"deadline_misses fifo={miss_f} slo={miss_s} "
         f"tokens/s fifo={toks/dt_f:.1f} slo={toks/dt_s:.1f} "
         "token_identical=True preps_after_construction=0")


def bench_serve_overload():
    """Overload survival at 4x: FIFO vs SLO vs SLO+preemption on a bursty
    two-tenant trace.

    Eight long best-effort "bulk" requests burst in at t=0 — four times
    the slot count — and pin both slots for the whole horizon; three
    short, deadline-tight "gold" 2/2 requests trickle in behind them.  No
    slot frees before the gold deadlines, so admission-order policies
    cannot save them: FIFO serves the backlog in order (gold waits out
    the ENTIRE bulk queue), plain SLO reorders the queue but still has to
    wait for a free slot, and only SLO+preemption displaces a running
    bulk request (snapshotting its KV lane for a later prefill-free
    resume) to run gold immediately.  Asserts (acceptance criteria):
    token-identity across all three policies — in particular every
    preempted-and-resumed bulk request is bit-identical to its
    uninterrupted runs under FIFO/SLO — zero deadline misses for
    deadline-bearing requests under SLO+preemption, and strictly lower
    p99 queue-wait for them than under either FIFO or plain SLO."""
    from repro.configs import reduced_config
    from repro.core.policy import uniform_schedule
    from repro.models.layers import Runtime
    from repro.models.transformer import LM
    from repro.serve import Request, ServeEngine, SLOPolicy

    cfg = reduced_config("granite-3-8b")
    model = LM(cfg)
    rng = np.random.default_rng(23)
    params = model.init(jax.random.PRNGKey(0))
    tiers = {"8/8": (8, 8), "4/4": (4, 4), "2/2": (2, 2)}
    sched = uniform_schedule(tiers, backend="decomposed")
    rt = Runtime(policy=sched.policy_for(), mode="serve", moe_dropless=True,
                 schedule=sched)

    def req(uid, budget, tier, deadline, tenant):
        return Request(uid=uid,
                       prompt=rng.integers(0, cfg.vocab_size, size=4 + uid),
                       max_new_tokens=budget, tier=tier, deadline=deadline,
                       tenant=tenant)

    # 4x overload: 8 best-effort requests burst onto 2 slots at t=0; the
    # urgent gold tail arrives once both slots are already pinned for a
    # full 24-tick wave, so no slot frees before the gold deadlines and
    # only displacement can serve them in time.
    arrivals = [(0.0, req(i, 24, t, None, "bulk"))
                for i, t in enumerate(["8/8", "4/4"] * 4)]
    arrivals += [(2.0, req(8, 2, "2/2", 14.0, "gold")),
                 (4.0, req(9, 2, "2/2", 14.0, "gold")),
                 (6.0, req(10, 2, "2/2", 16.0, "gold"))]

    store = {}

    def serve(policy):
        eng = ServeEngine(model, store.get("params", params), rt,
                          max_batch=2, max_len=64, decode_chunk=4,
                          scheduler_policy=policy)
        store["params"] = eng.params          # share the superplane store
        pending = list(arrivals)
        t0 = time.perf_counter()
        while pending or eng.has_work:
            while pending and (pending[0][0] <= eng.clock
                               or not eng.has_work):
                eng.submit(pending.pop(0)[1])
            eng.step()
        dt = time.perf_counter() - t0
        urgent = [h for h in eng.handles.values()
                  if h.request.deadline is not None]
        waits = np.array([h.queue_wait for h in urgent])
        misses = sum(1 for h in urgent
                     if h.finished_at > h.submitted_at + h.request.deadline)
        return eng.results, waits, misses, eng.stats, dt

    got_f, waits_f, miss_f, _, dt_f = serve(None)               # FIFO
    got_s, waits_s, miss_s, _, dt_s = serve(SLOPolicy(sched))   # plain SLO
    got_p, waits_p, miss_p, st_p, dt_p = serve(
        SLOPolicy(sched, preempt=True, preempt_slack=8.0))
    assert got_s == got_f and got_p == got_f, \
        "a preempted-and-resumed stream diverged from its uninterrupted run"
    assert st_p.preemptions > 0 and st_p.resumes == st_p.preemptions
    p99_f, p99_s, p99_p = (float(np.percentile(w, 99))
                           for w in (waits_f, waits_s, waits_p))
    assert miss_p == 0, f"preemption still missed {miss_p} gold deadlines"
    assert p99_p < p99_s < p99_f, (p99_p, p99_s, p99_f)
    toks = sum(len(v) for v in got_f.values())
    _row("serve_overload", (dt_f + dt_s + dt_p) * 1e6 / (3 * len(arrivals)),
         f"gold_p99_queue_wait fifo={p99_f:.0f} slo={p99_s:.0f} "
         f"slo+preempt={p99_p:.0f} (decode-step ticks) "
         f"deadline_misses fifo={miss_f} slo={miss_s} slo+preempt={miss_p} "
         f"preemptions={st_p.preemptions} resumes={st_p.resumes} "
         f"tokens/s fifo={toks/dt_f:.1f} preempt={toks/dt_p:.1f} "
         "token_identical=True")


def bench_autoprec_search():
    """Hardware-aware automatic mixed-precision search (repro.autoprec):
    Pareto front of avg bits vs modeled cycles vs measured divergence.

    Profiles every layer of a small config through the REAL quantization
    path (batched one-pass row groups over the superplane store), runs both
    search strategies (greedy marginal-divergence-per-cycle + MixPrec-style
    differentiable relaxation), jointly re-measures three front points, and
    asserts the acceptance invariants: even truncatable widths only, and a
    selected point that Pareto-dominates the uniform-8 baseline on modeled
    cycles at small measured divergence."""
    from repro.autoprec import (CostModel, measure_divergence, pareto_front,
                                profile_sensitivity, random_calibration,
                                schedule_from_results, search)
    from repro.configs import reduced_config
    from repro.core.decompose import RUNTIME_W_BITS
    from repro.core.policy import uniform_schedule
    from repro.models.transformer import LM
    from repro.serve import prepare_params

    cfg = reduced_config("granite-3-8b")
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    params, _ = prepare_params(
        params, uniform_schedule({"8/8": (8, 8)}).prepare_policy(),
        model, superplane=True)
    calib = random_calibration(cfg, batches=1, batch=2, seq=8, seed=5)
    choices = (2, 4, 6)

    t0 = time.perf_counter()
    profile = profile_sensitivity(model, params, calib=calib,
                                  choices=choices, block=8)
    cost = CostModel.for_config(cfg)
    front = search(profile.table, cost, choices=choices, strategy="both")
    pts = [front[0], front[len(front) // 2], front[-1]]
    meas = measure_divergence(model, params,
                              {f"p{i}": r.assignment
                               for i, r in enumerate(pts)}, calib=calib)
    for i, r in enumerate(pts):
        r.measured_divergence = meas[f"p{i}"]
    us = (time.perf_counter() - t0) * 1e6

    assert front, "empty Pareto front"
    assert all(b in RUNTIME_W_BITS for r in front
               for b in r.assignment.values()), "non-truncatable width"
    uniform8 = cost.uniform_cycles(8)
    best = pts[-1]
    assert best.cycles_per_token < uniform8, (best.cycles_per_token, uniform8)
    assert best.measured_divergence < 0.1, best.measured_divergence
    schedule_from_results([best])       # must emit a valid schedule
    front = pareto_front(front)
    _row("autoprec_search", us,
         f"front={len(front)}pts "
         "avg_bits/cycles/meas_div={"
         + " ".join(f"{r.avg_bits:.2f}b:{r.cycles_per_token:.0f}cyc:"
                    f"{r.measured_divergence:.1e}" for r in pts)
         + "} " + f"uniform8={uniform8:.0f}cyc "
         f"dominates_uniform8=True")


def bench_serve_tp_scaling():
    """Tensor-parallel sharded serving (``ServeEngine(mesh=...)``): one
    mixed 8/4/2 request stream served at 1-, 2- and 4-device meshes.

    Runs in a subprocess with 4 fake CPU devices (XLA_FLAGS).  Asserts
    (acceptance criteria): every mesh width is TOKEN-IDENTICAL to the
    unsharded engine, and the quantized wire moves <= 1/4 of the f32
    baseline's bytes per gathered activation element at the 8-bit tier —
    proportionally less at 4/2-bit, where codes travel bit-packed.
    Reports tokens/s and analytic wire bytes per decode step per mesh."""
    import json as _json
    import subprocess
    import sys
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    # The child's devices are fake CPU ones: pin its platform so it never
    # reaches for an accelerator this process may already hold.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    body = textwrap.dedent("""
        import dataclasses, json, time
        import jax, numpy as np
        from repro.configs import reduced_config
        from repro.core.policy import uniform_schedule
        from repro.distributed import tp_serve
        from repro.launch.mesh import make_serve_mesh
        from repro.models.layers import Runtime
        from repro.models.transformer import LM
        from repro.serve import Request, ServeEngine

        # num_kv_heads=4 so KV genuinely shards at n=2 and n=4 (the
        # reduced GQA configs often collapse to MQA).
        cfg = dataclasses.replace(reduced_config("granite-3-8b"),
                                  num_kv_heads=4)
        model = LM(cfg)
        params = model.init(jax.random.PRNGKey(0))
        tiers = {"8/8": (8, 8), "4/4": (4, 4), "2/2": (2, 2)}
        sched = uniform_schedule(tiers, backend="decomposed",
                                 kv_tiers={"8/8": None, "4/4": 8,
                                           "2/2": 4})
        rt = Runtime(policy=sched.policy_for(), mode="serve",
                     moe_dropless=True, schedule=sched)
        names = list(tiers)

        def requests(base):
            rng = np.random.default_rng(23)
            return [Request(uid=base + i,
                            prompt=rng.integers(0, cfg.vocab_size,
                                                size=3 + i % 5),
                            max_new_tokens=(8, 6, 7, 5, 8)[i],
                            tier=names[i % 3]) for i in range(5)]

        def serve(mesh):
            eng = ServeEngine(model, params, rt, max_batch=4, max_len=64,
                              decode_chunk=4, mesh=mesh)
            eng.run(requests(0))        # compile warm-up, same layouts
            t0 = time.perf_counter()
            got = eng.run(requests(100))
            dt = time.perf_counter() - t0
            return got, dt, eng

        ref, dt_ref, _ = serve(None)
        toks = sum(len(v) for v in ref.values())
        # A representative full-occupancy mixed layout for the analytic
        # wire cost: 2 slots at 8/8, one each at 4/4 and 2/2.
        layout = ((2, 8), (1, 4), (1, 2))
        out = {"tokens": toks, "meshes": {}}
        for n in (1, 2, 4):
            got, dt, eng = serve(make_serve_mesh(n))
            assert got == ref, f"mesh {n} diverged from unsharded tokens"
            tp = eng._tp
            assert tp is not None and tp.n == n
            assert n == 1 or tp.kv_shards
            stats = tp_serve.decode_wire_stats(cfg, tp, layout)
            for rows, bits in layout:   # the bit-serial wire law
                bpe = tp_serve.wire_bytes_per_element(bits)
                assert bpe <= 4.0 / 4.0 * (bits / 8.0 if bits < 8
                                           else 1.0), (bits, bpe)
            assert stats["vs_f32"] == 0 or stats["vs_f32"] >= 4.0
            out["meshes"][n] = {
                "tokens_per_s": toks / dt,
                "wire_bytes_per_step": stats["quant_gather_bytes"],
                "out_bytes_per_step": stats["out_gather_bytes"],
                "bytes_per_element": stats["bytes_per_element"],
                "vs_f32": stats["vs_f32"],
                "kv_shards": tp.kv_shards,
            }
        out["tokens_per_s_unsharded"] = toks / dt_ref
        print("TP_SCALING_JSON " + json.dumps(out))
    """)
    t0 = time.perf_counter()
    r = subprocess.run([sys.executable, "-c", body], capture_output=True,
                       text=True, env=env, timeout=1800)
    us = (time.perf_counter() - t0) * 1e6
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    line = next(l for l in r.stdout.splitlines()
                if l.startswith("TP_SCALING_JSON "))
    res = _json.loads(line.split(" ", 1)[1])
    per_mesh = " ".join(
        f"n{n}:{m['tokens_per_s']:.1f}tok/s:"
        f"{m['wire_bytes_per_step']:.0f}B/step"
        for n, m in sorted(res["meshes"].items(), key=lambda kv: int(kv[0])))
    bpe = res["meshes"]["2"]["bytes_per_element"]
    vs = res["meshes"]["2"]["vs_f32"]
    _row("serve_tp_scaling", us,
         f"tokens/s unsharded={res['tokens_per_s_unsharded']:.1f} "
         + per_mesh + f" wire_bytes/elem@n2={bpe:.3f} vs_f32={vs:.1f}x "
         "(8-bit rows 4x, 4-bit 8x, 2-bit 16x) token_identical=True")


def bench_dryrun_roofline_summary():
    """Summarize the multi-pod dry-run roofline table if results exist."""
    res_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "results", "dryrun")
    if not os.path.isdir(res_dir):
        _row("dryrun_roofline", 0.0, "no results (run repro.launch.dryrun_all)")
        return
    from repro.launch.roofline import load_cells, roofline_terms
    t0 = time.perf_counter()
    cells = load_cells(res_dir)
    live = [c for c in cells if not c.get("skipped")]
    doms = {}
    for c in live:
        t = roofline_terms(c)
        doms[t["dominant"]] = doms.get(t["dominant"], 0) + 1
    us = (time.perf_counter() - t0) * 1e6
    _row("dryrun_roofline", us,
         f"cells={len(cells)} live={len(live)} "
         f"skipped={len(cells)-len(live)} dominant={doms}")


def bench_spec_decode():
    """Self-speculative decoding: draft at the plane prefix, verify at
    8-bit in one batched forward.

    Asserts (the PR's acceptance criteria): greedy speculative streams
    token-identical to the non-speculative engine at the verify tier for
    k in {2, 4}; zero prepare_params calls after construction (the draft
    model is a free plane-prefix read); and FEWER verify-tier decode
    steps per emitted token than the one-step-per-token baseline
    (demonstrated deterministically with draft == verify tier, where
    acceptance is exactly 1.0, and measured at the 4-bit draft tier)."""
    from repro.configs import reduced_config
    from repro.core.policy import uniform_schedule
    from repro.models.layers import Runtime
    from repro.models.transformer import LM
    from repro.serve import engine as engine_mod
    from repro.serve.engine import Request, ServeEngine
    from repro.spec import SpecConfig

    cfg = reduced_config("granite-3-8b")
    model = LM(cfg)
    rng = np.random.default_rng(23)
    params = model.init(jax.random.PRNGKey(0))
    sched = uniform_schedule({"8/8": (8, 8), "4/4": (4, 4), "2/2": (2, 2)},
                             backend="decomposed",
                             kv_tiers={"8/8": 8, "4/4": 8, "2/2": 8})
    rt = Runtime(policy=sched.policy_for(), mode="serve", moe_dropless=True,
                 schedule=sched)
    prompts = [rng.integers(0, cfg.vocab_size, size=4 + i % 4)
               for i in range(3)]

    def serve(spec):
        eng = ServeEngine(model, params, rt, max_batch=3, max_len=64,
                          decode_chunk=4)
        preps = engine_mod.PREPARE_CALLS
        t0 = time.perf_counter()
        out = eng.run([Request(uid=i, prompt=p, max_new_tokens=9,
                               tier="8/8", spec=spec)
                       for i, p in enumerate(prompts)])
        dt = time.perf_counter() - t0
        assert engine_mod.PREPARE_CALLS == preps, \
            "weights were re-prepared after construction"
        return out, eng.stats, dt

    base, base_st, base_dt = serve(None)
    base_toks = sum(len(v) for v in base.values())
    for k in (2, 4):
        spec, st, dt = serve(SpecConfig(draft_tier="4/4", k=k))
        assert spec == base, f"k={k}: speculative stream diverged"
        acc = st.spec_accepted / max(st.spec_drafted, 1)
        _row(f"spec_decode_k{k}",
             dt * 1e6 / max(base_toks, 1),
             f"tokens/s={base_toks/dt:.1f} draft=4/4 "
             f"decode_steps={st.decode_steps} "
             f"base_decode_steps={base_st.decode_steps} "
             f"verify_steps/token={st.spec_verify_steps/st.spec_emitted:.2f} "
             f"accept_rate={acc:.2f} token_identical=True")
    # Full-acceptance row (draft == verify tier): acceptance is exactly
    # 1.0, so the verify-step saving is guaranteed, not weight-dependent.
    full, st, dt = serve(SpecConfig(draft_tier="8/8", k=4))
    assert full == base
    assert st.spec_verify_steps < st.spec_emitted, \
        "speculation must take fewer verify-tier steps than tokens emitted"
    assert st.decode_steps * 3 \
        == st.decode_slot_steps + st.decode_idle_slot_steps
    _row("spec_decode_full_accept",
         dt * 1e6 / max(base_toks, 1),
         f"tokens/s={base_toks/dt:.1f} draft=8/8 k=4 "
         f"decode_steps={st.decode_steps} "
         f"base_decode_steps={base_st.decode_steps} "
         f"verify_steps/token={st.spec_verify_steps/st.spec_emitted:.2f} "
         f"accept_rate={st.spec_accepted/max(st.spec_drafted,1):.2f} "
         f"token_identical=True")


BENCHES = {
    "table2_csa_vs_bat": bench_table2_csa_vs_bat,
    "table3_comparison": bench_table3_comparison,
    "fig7_breakdown": bench_fig7_breakdown,
    "fig8_energy_efficiency": bench_fig8_energy_efficiency,
    "mobilenetv2_power": bench_mobilenetv2_power,
    "mobilenetv2_throughput": bench_mobilenetv2_throughput,
    "kernel_bitserial_matmul": bench_kernel_bitserial_matmul,
    "kernel_packed_planes": bench_kernel_packed_vs_unpacked,
    "kernel_act_quant": bench_act_quant,
    "pe_array_utilization": bench_pe_array_utilization,
    "serve_continuous_batching": bench_continuous_batching,
    "serve_precision_tiers": bench_serve_precision_tiers,
    "serve_mixed_tiers": bench_serve_mixed_tiers,
    "serve_observability": bench_serve_observability,
    "fused_decode": bench_fused_decode,
    "serve_slo_scheduling": bench_serve_slo_scheduling,
    "serve_overload": bench_serve_overload,
    "serve_tp_scaling": bench_serve_tp_scaling,
    "spec_decode": bench_spec_decode,
    "autoprec_search": bench_autoprec_search,
    "dryrun_roofline": bench_dryrun_roofline_summary,
}


def main(argv=None) -> None:
    """Run all rows, or a subset: ``run.py --only name [name ...]``;
    ``run.py --list`` enumerates the available rows."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="+", choices=sorted(BENCHES),
                    help="run only these rows (CI smoke)")
    ap.add_argument("--list", action="store_true",
                    help="enumerate available rows (name: summary) and exit")
    ap.add_argument("--pr", default=os.environ.get("BENCH_PR", "10"),
                    metavar="N",
                    help="PR number stamped into the default --json "
                         "artifact name (BENCH_PR<N>.json; env BENCH_PR "
                         "overrides the built-in default)")
    ap.add_argument("--json", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="also persist the rows as a JSON artifact "
                         "(default path: BENCH_PR<--pr>.json)")
    args = ap.parse_args(argv)
    if args.json == "":
        args.json = f"BENCH_PR{args.pr}.json"
    if args.list:
        for name in sorted(BENCHES):
            doc = (BENCHES[name].__doc__ or "").strip().splitlines()
            print(f"{name}: {doc[0] if doc else ''}")
        return
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    names = args.only or list(BENCHES)
    print("name,us_per_call,derived")
    for name in names:
        BENCHES[name]()
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"rows": _RESULTS}, fh, indent=2)
            fh.write("\n")
        print(f"wrote {args.json} ({len(_RESULTS)} rows)")


if __name__ == "__main__":
    main()
