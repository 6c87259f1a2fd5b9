#!/usr/bin/env python3
"""Measurements that set a cell's data once, when the cell is defined; the
benchmark's own runs never run them.

    python3 perfbench/calibrate.py knee --workload W --seed S \\
        --rates 0.3 0.45 0.6 --segment 90
    python3 perfbench/calibrate.py limits --workload W --seeds 1 2 3 ... \\
        [--burst 16 --max-new 129]

``knee``: one set-up, then the cell's traffic (without its backlog) at
each rate in turn, one segment each, without draining in between; per segment the offered and
completed requests per second, tokens per second, the queue at its end and
the TTFT of the requests due in it.  The knee is the highest rate whose
queue does not grow.

``spread RESULT...``: the result lines of a set of runs (files whose last
line is a run's JSON result): per metric the median and the spread (first
to third quartile over the median, ``statistics.quantiles``), from which a
bound is about five times the widest spread over the cells.

``limits``: one set-up; for each seed, that seed's weights are packed into
the engine's store and a burst of the seed's first ``--burst`` requests
(outputs capped at ``--max-new`` tokens) is served through the same
programs as a run.  Every served token is then scored against the plain
reference, and the control (the reference one precision step lower) is
scored on the same positions: per tier and over all tiers (``all``, the
number a run compares), the widest gap, its 90th percentile and median,
and the share of positions whose served (control) token is the reference's
best.  One JSON line per seed.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Sequence  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _setup(workload: str, seed: int) -> Any:
    import jax
    from perfbench import harness, run
    cell = harness.load_cell(workload)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: no TPU")
    run.use_cache(jax)
    r = harness.Run(cell, seed, 0.0, False, T_START, harness.CompileClock())
    r.build()
    r.warm()
    harness.log(f"set-up {time.perf_counter() - T_START:.1f}s")
    return r


def knee(args: argparse.Namespace) -> None:
    from perfbench import stats, traffic
    from repro.serve import Request
    r = _setup(args.workload, args.seed)
    eng, mix = r.engine, dict(r.cell.mix, backlog=0)
    served: Dict[int, stats.Served] = {}
    uid = 0
    t0 = time.perf_counter()
    for k, rate in enumerate(args.rates):
        plan = traffic.generate(mix, rate, args.seed + k, args.segment,
                                r.cfg["vocab_size"])
        s0 = time.perf_counter()
        i = 0
        done0 = sum(1 for x in served.values() if x.finished)
        tok0 = sum(len(x.tokens) for x in served.values())
        q0 = eng.scheduler.queue_depth
        while time.perf_counter() < s0 + args.segment:
            now = time.perf_counter()
            while i < len(plan) and s0 + plan[i].due_s <= now:
                p = plan[i]
                served[uid] = stats.Served(uid=uid, tier=p.tier,
                                           due=s0 + p.due_s,
                                           prompt_len=len(p.prompt),
                                           max_new=p.max_new)
                eng.submit(Request(uid=uid, prompt=p.prompt,
                                   max_new_tokens=p.max_new, tier=p.tier))
                uid += 1
                i += 1
            if eng.has_work:
                events = eng.step()
                t = time.perf_counter()
                for ev in events:
                    served[ev.uid].tokens.append(ev.token)
                    served[ev.uid].token_times.append(t)
            else:
                time.sleep(0.01)
        s1 = time.perf_counter()
        done = sum(1 for x in served.values() if x.finished) - done0
        toks = sum(len(x.tokens) for x in served.values()) - tok0
        due = [x for x in served.values() if s0 <= x.due < s1]
        firsts = [(x.first - x.due) * 1e3 for x in due if x.first is not None]
        print(json.dumps({
            "rate": rate, "segment_s": s1 - s0, "offered": i / (s1 - s0),
            "completed_per_s": done / (s1 - s0), "tok_s": toks / (s1 - s0),
            "queue_start": q0, "queue_end": eng.scheduler.queue_depth,
            "running_end": len(eng.scheduler.occupied()),
            "first_tokens": f"{len(firsts)}/{len(due)}",
            "ttft_p50_ms": stats.percentile(firsts, 50) if firsts else None,
            "ttft_max_ms": max(firsts) if firsts else None,
            "since_start_s": s1 - t0}), flush=True)


def _gap_stats(ref: np.ndarray, chosen: np.ndarray) -> Dict[str, float]:
    from perfbench import harness
    gaps = harness.token_gaps(ref, chosen)
    return {"max": float(gaps.max()), "p90": float(np.percentile(gaps, 90)),
            "median": float(np.median(gaps)),
            "agree": float((ref.argmax(axis=1) == chosen).mean()),
            "n": int(len(chosen))}


def limits(args: argparse.Namespace) -> None:
    import jax
    from perfbench import harness, traffic, weights
    from repro.serve import Request
    from repro.serve.engine import prepare_params
    r = _setup(args.workload, args.seeds[0])
    eng, mix, cfg = r.engine, r.cell.mix, r.cfg
    ref = harness.load_module("references", cfg["reference"])
    shapes = jax.eval_shape(r.model.init, jax.random.PRNGKey(0))
    uid = 0
    for seed in args.seeds:
        t0 = time.perf_counter()
        if seed != args.seeds[0]:
            eng.params = None
            gc.collect()
            floats = weights.make_params(shapes, seed)
            eng.params, _ = prepare_params(floats, r.sched.prepare_policy(),
                                           r.model, packed=True,
                                           superplane=True)
            del floats
            gc.collect()
        plan = traffic.generate(dict(mix, backlog=0), 1.0, seed, args.burst,
                                cfg["vocab_size"])[:args.burst]
        handles = []
        for p in plan:
            handles.append((p, eng.submit(Request(
                uid=uid, prompt=p.prompt, tier=p.tier,
                max_new_tokens=min(p.max_new, args.max_new)))))
            uid += 1
        while eng.has_work:
            eng.step()
        t1 = time.perf_counter()
        seqs, targets, tiers = [], [], []
        for p, h in handles:
            toks = list(h.tokens)
            seqs.append({"tokens": np.concatenate(
                [p.prompt, np.asarray(toks[:-1], np.int32)]),
                "tier": p.tier, "score_from": len(p.prompt) - 1})
            targets.extend(toks)
            tiers.extend([p.tier] * len(toks))
        targets_a, tiers_a = np.asarray(targets), np.asarray(tiers)
        stated = np.asarray(ref.logits(cfg, seed, seqs))
        control = np.asarray(ref.logits(cfg, seed, seqs, mode="control"))
        out: Dict[str, Any] = {"seed": seed, "serve_s": t1 - t0,
                               "reference_s": time.perf_counter() - t1}
        masks = {t: tiers_a == t for t in cfg["tiers"]}
        masks["all"] = np.ones(len(tiers_a), bool)      # the compared number
        for t, m in masks.items():
            if m.any():
                out[t] = {"program": _gap_stats(stated[m], targets_a[m]),
                          "control": _gap_stats(stated[m],
                                                control[m].argmax(axis=1))}
        print(json.dumps(out), flush=True)
        del stated, control


def spread(args: argparse.Namespace) -> None:
    from perfbench import stats
    values: Dict[str, List[float]] = {}
    for path in args.results:
        with open(path) as f:
            res = json.loads(f.read().strip().splitlines()[-1])
        for name, m in res["metrics"].items():
            values.setdefault(name, []).append(m["value"])
    for name, v in values.items():
        print(json.dumps({"metric": name, "runs": len(v),
                          "median": float(np.median(v)),
                          "spread": stats.spread(v) if len(v) > 1 else None}))


def main(argv: Sequence[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    k = sub.add_parser("knee")
    k.add_argument("--workload", required=True)
    k.add_argument("--seed", type=int, default=1)
    k.add_argument("--rates", type=float, nargs="+", required=True)
    k.add_argument("--segment", type=float, default=90.0)
    lim = sub.add_parser("limits")
    lim.add_argument("--workload", required=True)
    lim.add_argument("--seeds", type=int, nargs="+", required=True)
    lim.add_argument("--burst", type=int, default=16)
    lim.add_argument("--max-new", type=int, default=129)
    sp = sub.add_parser("spread")
    sp.add_argument("results", nargs="+")
    args = ap.parse_args(argv)
    {"knee": knee, "limits": limits, "spread": spread}[args.what](args)


if __name__ == "__main__":
    main()
