#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 perfbench/run.py --workload granite-3-8b.mixed-decode-over \\
        --seed 7 --seconds 40 --trace 0

The cell, its configuration, traffic mix, rate and check limits are data
(see ``harness.py``).  The process builds the served model from ``--seed``,
warms every shape the cell's traffic uses, drives the open loop for
``--seconds`` (``--trace 1``: traces a few seconds of it instead and reports
the per-layer metrics), then checks a sample of the served tokens against
the plain reference.  Its last stdout line is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), and last ``checks``, each compared number beside its limit.
It exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Callable, Dict, Optional, Sequence  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def parse(argv: Optional[Sequence[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_cache(jax: Any) -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``repro.launch.compile_cache``), holding every program however small
    or quick to compile, so that a second run compiles nothing."""
    from repro.launch.compile_cache import use_compile_cache
    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv: Optional[Sequence[str]] = None, *, cell: Any = None,
         require_chip: bool = True,
         tamper: Optional[Callable[[Any], None]] = None) -> int:
    args = parse(argv)
    import jax
    from perfbench import costs, harness, stats, trace
    log = harness.log
    cell = cell or harness.load_cell(args.workload)
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            log(f"run: JAX found no TPU ({devices[0].platform}); no result")
            return 1
        if len(devices) < cell.chips:
            log(f"run: {cell.name} needs {cell.chips} chips, JAX found "
                f"{len(devices)}; no result")
            return 1
        log(f"compile cache: {use_cache(jax)}")
    kind = devices[0].device_kind
    pk = costs.peaks(kind if require_chip else "TPU v5 lite")
    clock = harness.CompileClock()
    run = harness.Run(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, clock, tamper)
    run.build()
    run.warm()
    log(f"warm: {time.perf_counter() - T_START:.1f}s since start, compile "
        f"{clock.seconds:.1f}s, {clock.cache_hits} cache hits")
    run.serve()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices()[:cell.chips])
    e2e = run.end_to_end()
    ws, we = run.window
    late = sorted(run.lateness)
    log(f"window {we - ws:.3f}s; generator late p50 "
        f"{stats.percentile(late, 50) * 1e3:.3f} ms, p95 "
        f"{stats.percentile(late, 95) * 1e3:.3f} ms, max "
        f"{late[-1] * 1e3:.3f} ms over {len(late)} submits")
    log("end to end: " + json.dumps(e2e))
    q = run.queue_in_window
    log(f"queue in the window: min {min(q, default=0)}, max "
        f"{max(q, default=0)}; compiles in the window "
        f"{run.clock.lowered_between(ws, we)}")
    due = [u for u, r in run.served.items() if ws <= r.due < we]
    attempted = len(due)
    failed = sum(1 for u in due if run.handles[u].status.name == "SHED")
    result: Dict[str, Any] = {"correct": False, "attempted": attempted,
                              "failed": failed}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    if args.trace:
        raw = trace.load(run.logdir)
        log("trace: device planes " + json.dumps(
            {k: len(v) for k, v in raw["ops"].items()}) + ", host spans "
            + str(len(raw["host"])))
        summary = trace.reduce(raw)
        shutil.rmtree(run.logdir, ignore_errors=True)
        run.trace_summary = summary
        rec = run.layer_record(pk)
        metrics = {}
        for m in cell.per_layer:
            value = harness.load_module("metrics", m["name"]).read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
        log("kernel calls: " + json.dumps(dict(rec["work"].calls)) +
            " events: " + json.dumps({k: trace.matching(
                summary["op_count"], v) for k, v in rec["kernels"].items()}))
        log("top ops: " + json.dumps(sorted(
            ([n, summary["op_time"][n], summary["op_count"][n]]
             for n in summary["op_time"]), key=lambda x: -x[1])[:25]))
        log("programs: " + json.dumps({k: [summary["module_time"][k],
                                           summary["module_count"][k]]
                                       for k in summary["module_time"]}))
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
        breakdown = None
    run.free()
    t_check = time.perf_counter()
    checks = run.check()
    log(f"check took {time.perf_counter() - t_check:.1f}s")
    correct = bool(checks) and all(v["value"] <= v["limit"]
                                   for v in checks.values())
    result.update(correct=correct, metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, v in checks.items():
        log(f"check {k} = {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
