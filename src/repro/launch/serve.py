"""Serving driver: offline-quantize a model (Table-I planes, optionally
packed) and serve a stream of greedy-decode requests through the streaming
engine API — ``submit() -> RequestHandle`` / ``step() -> [TokenEvent]`` /
``drain()`` (`--baseline` runs the batch-at-a-time reference engine).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
        --w-bits 4 --kv-bits 8 --requests 8

Runtime-reconfigurable tiers (one 8-bit superplane preload, per-request
effective precision; requests round-robin over the tiers and decode in
MIXED-tier batches — one jitted step serves all tiers at once):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
        --tiers 8/8 4/4 2/2 --requests 9

Per-request KV-cache precision (one kv value per tier, aligned with
--tiers; bf16 / 8 / 4) and the tier-serialized admission baseline:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
        --tiers 8/8 4/4 2/2 --kv-tiers bf16 8 4 --requests 9
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
        --tiers 8/8 4/4 2/2 --serialize-tiers --requests 9

SLO-aware admission (deadline slack priced by the hwmodel's per-tier cycle
cost instead of plain FIFO; every 3rd request gets a tight deadline) and
mid-stream tier migration (the first live request is migrated to the LAST
--tiers entry after a few tokens — KV lane requantized in place, weight
plane prefix switched at the next group layout):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
        --tiers 8/8 4/4 2/2 --slo --requests 9
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
        --tiers 8/8 4/4 2/2 --kv-tiers bf16 8 4 --migrate-demo --requests 6

Overload survival on top of --slo: ``--preempt`` lets a deadlined request
that ran out of slack displace the slackest running slot (the victim's
KV/SSM slice is snapshotted host-side and later resumes prefill-free,
token-identical); ``--shed`` turns admission into overload control — a
deadline request whose projected completion exceeds modeled capacity is
refused at submit (terminal SHED status) instead of missing late:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
        --tiers 8/8 4/4 2/2 --slo --preempt --shed --requests 12

Self-speculative decoding from the plane prefix (--speculate): every
request drafts --spec-k tokens per round at the --draft-tier plane prefix
of the SAME superplane store, verifies the window in ONE batched forward
at its own tier, and rolls rejected positions back — greedy streams are
token-identical to non-speculative decoding at the verify tier.
--temperature/--top-k switch the whole stream to seeded stochastic
sampling (deterministic across eager/jit and mesh widths):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
        --tiers 8/8 4/4 --speculate --draft-tier 4/4 --spec-k 4 --requests 6
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
        --tiers 8/8 4/4 --temperature 0.8 --top-k 40 --requests 6
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Optional

import jax
import numpy as np

from repro.configs import get_config, reduced_config
from repro.core.policy import (PrecisionPolicy, PrecisionSchedule,
                               uniform_policy, uniform_schedule)
from repro.launch.compile_cache import use_compile_cache
from repro.models.layers import Runtime
from repro.models.transformer import LM
from repro.serve import (BatchServeEngine, Request, ServeEngine, SLOPolicy,
                         prepare_params)
from repro.serve.engine import params_prepared
from repro.serve.handle import RequestStatus
from repro.telemetry import Telemetry, serve_report, write_json, xplane


def build_engine(model: LM, params: Any, *, policy: PrecisionPolicy,
                 schedule: Optional[PrecisionSchedule] = None,
                 packed: bool = False, baseline: bool = False,
                 moe_dropless: bool = False, max_batch: int,
                 max_len: int, kv_bits: Optional[int] = None,
                 telemetry: Optional[Telemetry] = None,
                 **serve_kwargs: Any) -> Any:
    """The serving stack's construction path, shared by this CLI and
    ``chip_smoke.py``: weight preload, runtime, engine.

    Float ``params`` are prepared once here (the 8-bit superplane store
    when a ``schedule`` is given); already-prepared params are served as
    they are, so two engines can share one store.  ``baseline`` builds the
    batch-at-a-time reference engine; ``serve_kwargs`` go to
    ``ServeEngine``."""
    if policy.default.backend != "dense" and not params_prepared(params):
        t0 = time.time()
        params, qpaths = prepare_params(
            params, schedule.prepare_policy() if schedule else policy,
            model, packed=packed, superplane=schedule is not None)
        kind = "superplane" if schedule else f"w{policy.default.w_bits}"
        print(f"prepared {len(qpaths)} weights ({kind}, packed={packed}) "
              f"in {time.time()-t0:.1f}s")
    rt = Runtime(policy=policy, mode="serve", moe_dropless=moe_dropless,
                 schedule=schedule)
    if baseline:
        return BatchServeEngine(model, params, rt, max_batch=max_batch,
                                max_len=max_len, kv_bits=kv_bits,
                                telemetry=telemetry)
    return ServeEngine(model, params, rt, max_batch=max_batch,
                       max_len=max_len, kv_bits=kv_bits,
                       telemetry=telemetry, **serve_kwargs)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--w-bits", type=int, default=4)
    ap.add_argument("--a-bits", type=int, default=8)
    ap.add_argument("--kv-bits", type=int, default=None)
    ap.add_argument("--packed", action="store_true")
    ap.add_argument("--backend", default="decomposed",
                    choices=["decomposed", "pallas", "dense"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--decode-chunk", type=int, default=8)
    ap.add_argument("--baseline", action="store_true",
                    help="use the batch-at-a-time reference engine")
    ap.add_argument("--tiers", nargs="+", default=None, metavar="W/A",
                    help="runtime precision tiers, e.g. --tiers 8/8 4/4 2/2: "
                         "ONE superplane preload, requests round-robin over "
                         "the tiers (even w only; overrides --w/a-bits)")
    ap.add_argument("--schedule-file", default=None, metavar="SCHEDULE.json",
                    help="serve a searched PrecisionSchedule written by "
                         "repro.launch.autoprec (tiers, per-layer rules and "
                         "kv_tiers come from the file; requests round-robin "
                         "over its tiers)")
    ap.add_argument("--kv-tiers", nargs="+", default=None, metavar="KV",
                    help="per-tier KV-cache precision aligned with --tiers "
                         "(bf16, 8 or 4): ONE mixed per-slot KV arena, each "
                         "request's slot stored at its tier's kv precision")
    ap.add_argument("--serialize-tiers", action="store_true",
                    help="tier-SERIALIZED admission (one tier per decode "
                         "batch; PR-2 behaviour) instead of mixed-tier "
                         "batches — the serve_mixed_tiers comparison "
                         "baseline")
    ap.add_argument("--slo", action="store_true",
                    help="SLO-aware admission (SLOPolicy): every 3rd "
                         "request gets a tight deadline; reports per-"
                         "request queue waits and deadline misses")
    ap.add_argument("--preempt", action="store_true",
                    help="with --slo: slot preemption — a deadlined "
                         "waiting request out of slack displaces the "
                         "slackest running slot (snapshot + prefill-free, "
                         "token-identical resume)")
    ap.add_argument("--shed", action="store_true",
                    help="with --slo: admission control — shed a deadline "
                         "request at submit when its projected completion "
                         "exceeds modeled capacity (with --auto-tier it is "
                         "downtiered first if a faster tier still fits)")
    ap.add_argument("--spill-dir", default=None, metavar="DIR",
                    help="spill preempted-slot snapshots through the "
                         "checkpoint subsystem (atomic step dirs under DIR) "
                         "instead of holding them host-resident")
    ap.add_argument("--auto-tier", action="store_true",
                    help="with --slo on a tiered engine: deadline-aware "
                         "tier auto-selection — a deadlined request is "
                         "retagged at admission to the best tier whose "
                         "priced service time fits its slack")
    ap.add_argument("--migrate-demo", action="store_true",
                    help="mid-stream tier migration demo: after a few "
                         "tokens the first live request is migrated to the "
                         "last --tiers entry (requantizes its KV lane in "
                         "place; needs --tiers, mixed admission)")
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="tensor-parallel serving over N devices: shard the "
                         "superplane store column-wise and the KV arena over "
                         "heads, with quantized (int8 / bit-packed) "
                         "activation gathers on the wire — token-identical "
                         "to the unsharded engine.  On CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N for fake "
                         "devices")
    ap.add_argument("--speculate", action="store_true",
                    help="self-speculative decoding: draft --spec-k tokens "
                         "per round at the --draft-tier plane prefix, "
                         "verify the window in one batched forward at each "
                         "request's own tier (greedy streams are token-"
                         "identical to non-speculative decoding; needs "
                         "--tiers, mixed admission)")
    ap.add_argument("--draft-tier", default=None, metavar="W/A",
                    help="with --speculate: the draft tier (must be one of "
                         "--tiers; default: the last, lowest-precision "
                         "--tiers entry)")
    ap.add_argument("--spec-k", type=int, default=4, metavar="K",
                    help="with --speculate: draft tokens per round "
                         "(default 4)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax, the "
                         "default); seeded, deterministic across eager/jit "
                         "and mesh widths")
    ap.add_argument("--top-k", type=int, default=0, metavar="K",
                    help="with --temperature > 0: restrict sampling to the "
                         "K highest-probability tokens (0 = full vocab)")
    ap.add_argument("--metrics", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="export the run's metrics: Prometheus text to "
                         "stdout (bare --metrics) or to PATH; a .json "
                         "suffix writes the JSON snapshot instead")
    ap.add_argument("--trace-out", default=None, metavar="TRACE.json",
                    help="write the dual-clock span trace as Chrome "
                         "trace-event JSON (loadable in Perfetto / "
                         "chrome://tracing)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="record a jax.profiler trace of the serve loop "
                         "under DIR (device time of each program, the "
                         "model's layer scopes and the engine's serve.* "
                         "spans on one clock; TensorBoard/XProf or "
                         "repro.telemetry.xplane read it) and print its "
                         "summary — bit-identical output")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # Flag validation BEFORE any model building (full-size configs take
    # minutes to init; a bad flag combination must fail instantly).
    schedule = None
    if args.schedule_file:
        if args.tiers:
            ap.error("--schedule-file carries its own tiers; drop --tiers")
        if args.kv_tiers:
            ap.error("--schedule-file carries its own kv_tiers; drop "
                     "--kv-tiers")
        if args.backend == "dense":
            ap.error("--schedule-file needs an integer backend")
        if args.baseline:
            ap.error("--baseline has no per-request tier switching; drop "
                     "--schedule-file")
        from repro.autoprec import load_schedule
        schedule = load_schedule(args.schedule_file)
        if schedule.kv_tiers is not None and args.kv_bits is not None:
            ap.error("--kv-bits conflicts with the schedule file's kv_tiers")
        file_backends = {p.backend for p in schedule._all_precisions()}
        if file_backends != {args.backend}:
            ap.error(f"--backend {args.backend} does not match the schedule "
                     f"file's backend(s) {sorted(file_backends)}; pass the "
                     "matching --backend (or re-emit the file with "
                     "repro.launch.autoprec --backend)")
        # Downstream request/reporting logic round-robins over the loaded
        # tier names exactly like hand-written --tiers.
        args.tiers = list(schedule.tier_names)
        policy = schedule.policy_for()
    elif args.tiers:
        if args.backend == "dense":
            ap.error("--tiers needs an integer backend")
        if args.baseline:
            ap.error("--baseline has no per-request tier switching "
                     "(it pins one tier); drop --tiers")
        kv_tiers = None
        if args.kv_tiers:
            if len(args.kv_tiers) != len(args.tiers):
                ap.error("--kv-tiers must align 1:1 with --tiers")
            if args.kv_bits is not None:
                ap.error("--kv-bits conflicts with --kv-tiers; drop one")
            try:
                kv_tiers = {t: (None if kv in ("bf16", "none") else int(kv))
                            for t, kv in zip(args.tiers, args.kv_tiers)}
            except ValueError:
                ap.error(f"--kv-tiers values must be bf16, 8 or 4, got "
                         f"{args.kv_tiers}")
        schedule = uniform_schedule(
            {t: tuple(int(b) for b in t.split("/")) for t in args.tiers},
            backend=args.backend, kv_tiers=kv_tiers)
        policy = schedule.policy_for()
    else:
        if args.kv_tiers:
            ap.error("--kv-tiers needs --tiers")
        if args.serialize_tiers:
            ap.error("--serialize-tiers needs --tiers")
        policy = uniform_policy(args.w_bits, args.a_bits,
                                backend=args.backend)
    if args.migrate_demo:
        if not args.tiers or len(args.tiers) < 2:
            ap.error("--migrate-demo needs --tiers with >= 2 tiers")
        if args.serialize_tiers or args.baseline:
            ap.error("--migrate-demo needs mixed-tier admission (drop "
                     "--serialize-tiers / --baseline)")
    if args.slo and args.baseline:
        ap.error("--slo has no effect on the batch-at-a-time baseline")
    if (args.preempt or args.shed) and not args.slo:
        ap.error("--preempt/--shed are SLOPolicy overload hooks; they need "
                 "--slo")
    if args.spill_dir and not args.preempt:
        ap.error("--spill-dir only stores preempted-slot snapshots; it "
                 "needs --preempt")
    if args.auto_tier and not args.slo:
        ap.error("--auto-tier needs --slo (it is SLOPolicy's admission "
                 "hook)")
    if args.auto_tier and (schedule is None or args.serialize_tiers):
        ap.error("--auto-tier needs runtime tiers with mixed admission "
                 "(--tiers/--schedule-file, no --serialize-tiers)")
    if args.speculate:
        if not args.tiers:
            ap.error("--speculate drafts at a plane-prefix tier; it needs "
                     "--tiers (or --schedule-file)")
        if args.serialize_tiers or args.baseline:
            ap.error("--speculate needs mixed-tier admission (drop "
                     "--serialize-tiers / --baseline)")
        if args.mesh:
            ap.error("--speculate is not supported on a mesh engine yet; "
                     "drop --mesh")
        if args.spec_k < 1:
            ap.error(f"--spec-k must be >= 1, got {args.spec_k}")
        if args.draft_tier is None:
            args.draft_tier = args.tiers[-1]
        elif args.draft_tier not in args.tiers:
            ap.error(f"--draft-tier {args.draft_tier} is not one of the "
                     f"serving tiers {args.tiers}")
    elif args.draft_tier is not None:
        ap.error("--draft-tier needs --speculate")
    if args.temperature < 0.0:
        ap.error(f"--temperature must be >= 0, got {args.temperature}")
    if args.top_k < 0:
        ap.error(f"--top-k must be >= 0, got {args.top_k}")
    if args.temperature > 0.0 and args.baseline:
        ap.error("--temperature needs the continuous-batching engine; the "
                 "baseline decodes greedily (drop --baseline)")
    mesh = None
    if args.mesh:
        if args.baseline:
            ap.error("--mesh needs the continuous-batching engine; drop "
                     "--baseline")
        if args.backend == "dense":
            ap.error("--mesh shards the quantized plane store; it needs an "
                     "integer backend (decomposed/pallas)")
        from repro.launch.mesh import make_serve_mesh
        try:
            mesh = make_serve_mesh(args.mesh)   # fail fast, pre model build
        except ValueError as e:
            ap.error(str(e))

    use_compile_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    # The driver always runs with telemetry attached (the zero-cost-when-
    # off contract matters for the library; a demo CLI can afford the
    # hooks) — the end-of-run report, --metrics and --trace-out all read
    # from it.
    tele = Telemetry()
    scheduler_policy = None
    if args.slo:
        # Rules-aware tier pricing: searched schedules (per-layer rule
        # tiers over a common default) only price differently when each
        # tier's per-layer widths are MAC-weighted.
        scheduler_policy = SLOPolicy(
            schedule, auto_tier=args.auto_tier,
            mac_counts=cfg.quant_layer_macs() if schedule else None,
            preempt=args.preempt,
            # Chunk granularity: a queued request can wait up to ~2 chunks
            # before the displacement check sees it again.
            preempt_slack=2.0 * args.decode_chunk,
            shed=args.shed)
    engine = build_engine(model, params, policy=policy, schedule=schedule,
                          packed=args.packed, baseline=args.baseline,
                          moe_dropless=args.reduced,
                          max_batch=args.max_batch, max_len=args.max_len,
                          kv_bits=args.kv_bits, telemetry=tele,
                          decode_chunk=args.decode_chunk,
                          mixed_tiers=not args.serialize_tiers,
                          scheduler_policy=scheduler_policy, mesh=mesh,
                          spill_dir=args.spill_dir)
    if mesh is not None:
        tp = engine._tp
        assert tp is not None
        print(f"mesh: {tp.n}-way tensor parallel "
              f"(kv_shards={tp.kv_shards}) over "
              f"{[d.platform for d in mesh.devices.flat]}")

    rng = np.random.default_rng(args.seed)
    tier_of = (lambda i: args.tiers[i % len(args.tiers)]) if args.tiers \
        else (lambda i: None)
    # --slo: a deadline-skewed stream — every 3rd request is urgent (a
    # tight budget in scheduler-clock ticks); the rest are patient.  With
    # --preempt/--shed the stream reshapes into a genuine overload trace:
    # patients become LONG best-effort hogs (the canonical preemption
    # victims — a slot never frees within an urgent deadline on its own),
    # the urgent tail gets deadlines of a few chunks and arrives
    # mid-flight (below) once the hogs pin every slot, and the LAST
    # urgent request carries a budget no tier can serve inside its
    # deadline — the fail-fast shed case.
    overload = args.preempt or args.shed
    urgent_deadline = (2.5 * args.decode_chunk
                       if overload else 4.0 * args.max_new)
    urgent_ids = [i for i in range(args.requests) if i % 3 == 2]
    deadline_of = (lambda i: urgent_deadline if i % 3 == 2
                   else None if overload else 50.0 * args.max_new) \
        if args.slo else (lambda i: None)

    def budget_of(i: int) -> int:
        if not overload:
            return 1 + (args.max_new * (i % 4)) // 3
        if i % 3 == 2:
            return (3 * args.max_new if urgent_ids and i == urgent_ids[-1]
                    else min(4, args.max_new))
        return 3 * args.max_new

    sampling = None
    if args.temperature > 0.0 or args.top_k > 0:
        from repro.spec import SamplingParams
        sampling = SamplingParams(temperature=args.temperature,
                                  top_k=args.top_k, seed=args.seed)
    spec = None
    if args.speculate:
        from repro.spec import SpecConfig
        spec = SpecConfig(draft_tier=args.draft_tier, k=args.spec_k)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=4 + i % 5),
                    max_new_tokens=budget_of(i),
                    tier=tier_of(i), deadline=deadline_of(i),
                    sampling=sampling, spec=spec)
            for i in range(args.requests)]

    # The streaming loop: submit, step until drained, stream tokens
    # through the handles' events.  Overload mode holds the urgent tail
    # back until the patient burst occupies the slots.
    t0 = time.time()
    urgent_tail = [r for r in reqs
                   if r.deadline is not None and r.deadline <= urgent_deadline] \
        if args.preempt or args.shed else []
    held = {r.uid for r in urgent_tail}
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    handles = [engine.submit(r) for r in reqs if r.uid not in held]
    migrated = None
    events = 0
    while engine.has_work or urgent_tail:
        events += len(engine.step())
        if urgent_tail and (engine.clock >= 2.0 * args.decode_chunk
                            or not engine.has_work):
            handles += [engine.submit(r) for r in urgent_tail]
            urgent_tail = []
        if args.migrate_demo and migrated is None:
            target = args.tiers[-1]
            for h in handles:
                if (h.status is RequestStatus.RUNNING and h.tier != target
                        and len(h.tokens) >= 2):
                    h.set_tier(target)
                    migrated = h
                    print(f"migrated uid={h.uid} -> {target} after "
                          f"{len(h.tokens)} tokens (clock {engine.clock:.0f})")
                    break
    dt = time.time() - t0
    if args.profile_dir:
        jax.profiler.stop_trace()
    if args.migrate_demo and migrated is None:
        print("migrate-demo: no request lived long enough to migrate — "
              "every budget fit one decode chunk; raise --max-new or "
              "lower --decode-chunk")
    results = {h.uid: h.tokens for h in handles}
    # Shed requests never reach engine.results — check the finished ones.
    assert all(results[h.uid] == engine.results[h.uid] for h in handles
               if h.status is RequestStatus.FINISHED)
    toks = sum(len(v) for v in results.values())
    print(f"served {len(reqs)} requests, {toks} tokens "
          f"({events} streamed events) in {dt:.2f}s ({toks/dt:.1f} tok/s)")
    # The per-section stat blocks this driver used to hand-format all read
    # from the telemetry registry now — the EngineStats twins plus the
    # derived gauges/histograms — so a stat prints here by being
    # registered, not by editing four format strings.
    print(serve_report(tele.registry, tiers=args.tiers,
                       mixed=not args.serialize_tiers, slo=args.slo,
                       speculate=args.speculate,
                       overload=args.preempt or args.shed))
    if args.preempt or args.shed:
        shed_uids = [h.uid for h in handles
                     if h.status is RequestStatus.SHED]
        print(f"shed_uids={shed_uids}")
    if args.profile_dir:
        print(f"profile: wrote {args.profile_dir}")
        print(xplane.format_summary(xplane.reduce(
            xplane.load(args.profile_dir))))
    if args.metrics is not None:
        if args.metrics == "-":
            print(tele.prometheus(), end="")
        elif args.metrics.endswith(".json"):
            write_json(args.metrics, tele.registry)
            print(f"metrics: wrote {args.metrics}")
        else:
            with open(args.metrics, "w") as fh:
                fh.write(tele.prometheus())
            print(f"metrics: wrote {args.metrics}")
    if args.trace_out:
        tele.write_trace(args.trace_out)
        print(f"trace: wrote {args.trace_out} "
              f"({len(tele.tracer.chrome_events())} events)")
    return results


if __name__ == "__main__":
    main()
