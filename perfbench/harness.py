"""One run of one cell: build the served model from the seed, warm every
shape the cell's traffic uses, drive the open loop through ``submit`` and
``step``, measure, and check what the timed path produced against the plain
reference.

Everything a cell is made of is data found by name: ``BENCHMARK.json``
names the cell's configuration and traffic mix; ``configs/<config>.json``,
``traffic/<mix>.json`` and ``cells/<cell>.json`` (its fixed rate and the
limits of its check) hold the numbers; ``metrics/<metric>.py`` reduces the
run's record to one per-layer number; ``references/<name>.py`` is the plain
reference a configuration names.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from perfbench import costs, stats, trace, traffic, weights

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 12.0         # traced part of a --trace 1 window, at least
TRACE_LIMIT_S = 60.0         # ... until a prefill and a decode chunk ran
DRAIN_LIMIT_S = 60.0         # wait past the window for the check's tokens
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
PROGRAMS = {"decode": "decode_chunk_fn", "prefill": "prefill_slot"}


def log(*parts: Any) -> None:
    print(*parts, file=sys.stderr, flush=True)


# ------------------------------------------------------------------- data
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    mix: Dict[str, Any]
    spec: Dict[str, Any]          # cells/<cell>.json: rate, check limits
    bench: Dict[str, Any]         # BENCHMARK.json

    @property
    def per_layer(self) -> List[Dict[str, Any]]:
        return [m for m in self.bench["per_layer"]
                if self.name in m.get("workloads", [self.name])]

    @property
    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]


def _json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    entry = [w for w in bench["workloads"] if w["name"] == name]
    if not entry:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = entry[0]
    cfg = [c for c in bench["configs"] if c["name"] == w["config"]][0]
    return Cell(name=name, chips=w["chips"],
                config=_json(os.path.join(root, cfg["file"])),
                mix=_json(os.path.join(HERE, "traffic", w["traffic"] + ".json")),
                spec=_json(os.path.join(HERE, "cells", name + ".json")),
                bench=bench)


def load_module(kind: str, name: str) -> Any:
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    assert spec is not None and spec.loader is not None
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ compiles
class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (a persistent
    cache read counts as the compile it replaces), persistent-cache hits,
    and the time of every lowering (one per new program), from
    ``jax.monitoring``."""

    def __init__(self) -> None:
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        self.lowerings: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **_: Any) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += duration_secs
        if event == LOWER_EVENT:
            self.lowerings.append(time.perf_counter())

    def _event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def lowered_between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.lowerings if t0 <= t <= t1)


# ------------------------------------------------------------ the model
def arch_config(cfg: Dict[str, Any]) -> Any:
    """The program's ``ArchConfig`` for a configuration file: its registry
    entry with the file's depth, RoPE base and head tying (the program's own
    options), every width checked against the file."""
    from repro.configs import get_config
    base = get_config(cfg["repro_arch"])
    widths = {"d_model": "hidden_size", "num_heads": "num_attention_heads",
              "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
              "d_ff": "intermediate_size", "vocab_size": "vocab_size"}
    for ours, theirs in widths.items():
        if getattr(base, ours) != cfg[theirs]:
            raise ValueError(f"{cfg['repro_arch']}: {ours} is "
                             f"{getattr(base, ours)}, the configuration "
                             f"says {theirs}={cfg[theirs]}")
    if bool(base.qk_norm) != bool(cfg.get("qk_norm")):
        raise ValueError("qk_norm differs from the configuration")
    arch = dataclasses.replace(
        base, num_layers=cfg["num_hidden_layers"],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]))
    if arch.padded_vocab != cfg["padded_vocab"]:
        raise ValueError("padded vocabulary differs from the configuration")
    return arch


def schedule(cfg: Dict[str, Any]) -> Any:
    from repro.core.policy import uniform_schedule
    tiers = {t: (v["w_bits"], v["a_bits"]) for t, v in cfg["tiers"].items()}
    kv = {t: (None if v["kv_bits"] == 16 else v["kv_bits"])
          for t, v in cfg["tiers"].items()}
    return uniform_schedule(tiers, backend="pallas", kv_tiers=kv)


def layouts(tier_names: Sequence[str], batch: int
            ) -> List[Tuple[Tuple[str, int], ...]]:
    """Every decode group layout of ``batch`` slots over the tiers; free
    slots ride in the first (default) tier's group, as in the engine."""
    out: List[Tuple[Tuple[str, int], ...]] = []

    def rec(i: int, left: int, counts: List[int]) -> None:
        if i == len(tier_names):
            full = [batch - sum(counts)] + counts
            out.append(tuple((t, c) for t, c in zip(tier_names, full) if c))
            return
        for c in range(left + 1):
            rec(i + 1, left - c, counts + [c])

    rec(1, batch, [])
    return out


def token_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far below the reference's best logit each served token's logit
    lies, row by row (0 where the served token is the reference's best)."""
    rows = np.arange(len(tokens))
    return ref_logits.max(axis=1) - ref_logits[rows, tokens]


# ------------------------------------------------------------------- run
@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    n_steps: int
    groups: Optional[Tuple[Tuple[str, int], ...]]
    prefills: List[Tuple[str, int]]          # (tier, prompt_len)
    contexts: List[int]                      # keys each decoded token saw


class Run:
    """One process's run of one cell."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace_on: bool,
                 t_start: float, clock: CompileClock,
                 tamper: Optional[Callable[[Any], None]] = None) -> None:
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace_on, self.t_start, self.clock = trace_on, t_start, clock
        self.tamper = tamper
        self.cfg = cell.config
        self.eng_kw = cell.mix["engine"]
        self.tier_names = list(self.cfg["tiers"])

    # -- set-up --------------------------------------------------------
    def build(self) -> None:
        import jax
        from repro.launch.serve import build_engine
        from repro.models.transformer import LM
        self.arch = arch_config(self.cfg)
        self.model = LM(self.arch)
        shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        params = weights.make_params(shapes, self.seed)
        sched = schedule(self.cfg)
        self.sched = sched
        self.engine = build_engine(
            self.model, params, policy=sched.policy_for(), schedule=sched,
            packed=True, max_batch=self.eng_kw["max_batch"],
            max_len=self.eng_kw["max_len"],
            decode_chunk=self.eng_kw["decode_chunk"],
            prompt_bucket=self.eng_kw["prompt_bucket"])
        del params
        gc.collect()
        if self.tamper is not None:
            self.tamper(self.engine)

    def warm(self) -> None:
        """Compile (or read from the cache) every program the cell's
        traffic can dispatch: a prefill per (tier, prompt bucket) and a
        decode chunk per group layout.  Where there is one layout, every
        trimmed chunk length too (the engine trims a chunk to the most
        steps any slot has left).  Where there are several, layouts times
        lengths are too many programs to warm: a trimmed chunk compiles
        where it comes and counts in ``compiles_in_window``."""
        import jax
        import jax.numpy as jnp
        eng = self.engine
        tiers = sorted(set(self.cell.mix["tiers"]), key=self.tier_names.index)
        for tier in tiers:
            for bucket in traffic.prefill_buckets(self.cell.mix):
                tok, caches = eng._prefill_slot(
                    eng.params, eng.arena.caches, jnp.int32(0),
                    jnp.asarray(np.zeros((1, bucket), np.int32)),
                    jnp.int32(bucket), jnp.int32(self.sched.kv_code_for(tier)),
                    jnp.asarray(eng._key[0]), jnp.float32(eng._temp[0]),
                    jnp.int32(eng._topk[0]), tier=tier)
                jax.block_until_ready(tok)
                del caches
        default = self.tier_names[0]
        perm = jnp.asarray(np.arange(eng.max_batch, dtype=np.int32))
        used = [g for g in layouts(self.tier_names, eng.max_batch)
                if all(t in tiers or t == default for t, _ in g)]
        chunk = self.eng_kw["decode_chunk"]
        steps = range(1, chunk + 1) if len(used) == 1 else (chunk,)
        for groups in used:
            for n in steps:
                out = eng._decode_chunk(
                    eng.params, eng.arena.caches, jnp.asarray(eng._tok),
                    jnp.asarray(eng._remaining), perm, n_steps=n, tier=None,
                    groups=groups, sampling=eng._sampling_args())
                jax.block_until_ready(out[1])
                del out

    # -- the open loop -------------------------------------------------
    def serve(self) -> None:
        import jax
        from repro.serve import Request
        eng = self.engine
        mix = self.cell.mix
        rate = float(self.cell.spec["rate_per_s"])
        lead = float(mix["lead_in_s"])
        run_s = TRACE_SECONDS if self.trace_on else self.seconds
        horizon = lead + run_s + DRAIN_LIMIT_S
        plan = traffic.generate(mix, rate, self.seed, horizon,
                                self.cfg["vocab_size"])
        self.prompts = {p.uid: p.prompt for p in plan}
        self.served: Dict[int, stats.Served] = {}
        self.steps: List[StepRecord] = []
        self.lateness: List[float] = []
        self.handles: Dict[int, Any] = {}
        self.queue_in_window: List[int] = []
        ann = jax.profiler.TraceAnnotation if self.trace_on else _null_span
        logdir = None
        t0 = self.t0 = time.perf_counter()
        ws = t0 + lead
        we = math.inf
        window_span: Any = None
        i = 0
        stats0: Dict[str, int] = {}
        stats1: Dict[str, int] = {}
        while True:
            now = time.perf_counter()
            if we == math.inf and now >= ws:
                if not stats0:            # the window opens between steps
                    if self.trace_on:
                        logdir = tempfile.mkdtemp(prefix="perfbench-trace")
                        jax.profiler.start_trace(logdir)
                        window_span = jax.profiler.TraceAnnotation(
                            trace.WINDOW_SPAN)
                        window_span.__enter__()
                        now = time.perf_counter()
                    ws = self.t_window = now
                    stats0 = self._stats()
                    self.compile_s = self.clock.seconds
                if now >= ws + run_s and self._window_full(ws, now):
                    we = now
                    stats1 = self._stats()
                    if window_span is not None:
                        window_span.__exit__(None, None, None)
                        jax.profiler.stop_trace()
            if we < math.inf and self._drained(ws, we, now, plan, i):
                break
            with ann("bench.submit"):
                while i < len(plan) and t0 + plan[i].due_s <= now:
                    p = plan[i]
                    self.served[p.uid] = stats.Served(
                        uid=p.uid, tier=p.tier, due=t0 + p.due_s,
                        prompt_len=len(p.prompt), max_new=p.max_new)
                    self.handles[p.uid] = eng.submit(Request(
                        uid=p.uid, prompt=p.prompt, max_new_tokens=p.max_new,
                        tier=p.tier))
                    self.lateness.append(time.perf_counter() - (t0 + p.due_s))
                    i += 1
            if stats0 and we == math.inf:
                self.queue_in_window.append(eng.scheduler.queue_depth)
            if eng.has_work:
                d0 = eng.stats.decode_steps
                s0 = time.perf_counter()
                with ann("bench.step"):
                    events = eng.step()
                s1 = time.perf_counter()
                self._record(events, s0, s1, eng.stats.decode_steps - d0)
            else:
                nxt = t0 + plan[i].due_s if i < len(plan) else now + 0.01
                if we == math.inf and now < ws:
                    nxt = min(nxt, ws)
                with ann("bench.wait"):
                    time.sleep(max(0.0, min(nxt, now + 0.05) - now))
        self.window = (ws, we)
        self.stats_delta = {k: stats1[k] - stats0[k] for k in stats0}
        self.logdir = logdir

    def _window_full(self, ws: float, now: float) -> bool:
        """A traced window holds at least one prefill and one decode chunk
        (or has run its limit); an untraced one is full at its length."""
        if not self.trace_on or now >= ws + TRACE_LIMIT_S:
            return True
        inside = [s for s in self.steps if s.t0 >= ws]
        return any(s.prefills for s in inside) and \
            any(s.n_steps for s in inside)

    def _drained(self, ws: float, we: float, now: float,
                 plan: Sequence[traffic.Planned], i: int) -> bool:
        """Past the window: stop once finished requests hold the tokens the
        check samples and, where the cell reports a TTFT, every request due
        in the window has its first token (or the drain limit passes)."""
        if now - we > DRAIN_LIMIT_S:
            return True
        enough = sum(len(r.tokens) for r in self.served.values()
                     if r.finished) >= self.cell.spec["check"]["served_tokens"]
        if not any(m["name"].startswith("ttft") for m in self.cell.end_to_end):
            return enough
        due_in = [r for r in self.served.values() if ws <= r.due < we]
        pending = i < len(plan) and self.t0 + plan[i].due_s < we
        return enough and not pending and \
            all(r.first is not None for r in due_in)

    def _stats(self) -> Dict[str, int]:
        st = self.engine.stats
        return {"decode_slot_steps": st.decode_slot_steps,
                "decode_idle_slot_steps": st.decode_idle_slot_steps,
                "decode_steps": st.decode_steps,
                "prefill_tokens": st.prefill_tokens,
                "prefills": st.prefills}

    def _record(self, events: Sequence[Any], s0: float, s1: float,
                n_steps: int) -> None:
        prefills: List[Tuple[str, int]] = []
        decoded: Dict[str, int] = {t: 0 for t in self.tier_names}
        contexts: List[int] = []
        uids = set()
        for ev in events:
            r = self.served[ev.uid]
            r.tokens.append(int(ev.token))
            r.token_times.append(s1)
            if ev.index == 0:
                prefills.append((r.tier, r.prompt_len))
            else:
                contexts.append(r.prompt_len + ev.index)
                if ev.uid not in uids:
                    uids.add(ev.uid)
                    decoded[r.tier] += 1
        groups = None
        if n_steps:
            b = self.engine.max_batch
            counts = [decoded[t] for t in self.tier_names]
            counts[0] += b - sum(counts)
            groups = tuple((t, c) for t, c in zip(self.tier_names, counts)
                           if c)
        self.steps.append(StepRecord(s0, s1, n_steps, groups, prefills,
                                     contexts))

    # -- after the window ----------------------------------------------
    def end_to_end(self) -> Dict[str, float]:
        ws, we = self.window
        reqs = list(self.served.values())
        out = stats.end_to_end(reqs, ws, we)
        out["setup_s"] = self.t_window - self.t_start
        return out

    def layer_record(self, pk: Dict[str, float]) -> Dict[str, Any]:
        ws, we = self.window
        inside = [s for s in self.steps if s.t0 >= ws and s.t1 <= we]
        chunks = [{"groups": s.groups, "n_steps": s.n_steps}
                  for s in inside if s.n_steps]
        prefills = [{"tier": t, "prompt_len": n}
                    for s in inside for t, n in s.prefills]
        work = costs.kernel_work(self.cfg, pk, chunks, prefills)
        dec_ctx = [c for s in inside for c in s.contexts]
        pre_ctx = [c for p in prefills for c in range(1, p["prompt_len"] + 1)]
        ops_decode = costs.model_ops(self.cfg, dec_ctx, len(dec_ctx))
        ops_prefill = costs.model_ops(self.cfg, pre_ctx, len(prefills))
        return {
            "cfg": self.cfg, "peaks": pk, "work": work,
            "kernels": _json(os.path.join(HERE, "kernels.json")),
            "programs": PROGRAMS,
            "trace": self.trace_summary,
            "compile_s": self.compile_s,
            "compiles_in_window": self.clock.lowered_between(ws, we),
            "stats_delta": self.stats_delta,
            "decode_steps": sum(s.n_steps for s in inside),
            "decode_tokens": len(dec_ctx),
            "prefill_tokens": sum(p["prompt_len"] for p in prefills),
            "ops": {"decode": ops_decode, "prefill": ops_prefill},
        }

    def free(self) -> None:
        del self.engine
        gc.collect()

    # -- the check -----------------------------------------------------
    def sample(self) -> List[stats.Served]:
        """Finished requests drawn from the seed: the one with the most
        served tokens, one of each tier, then more until the sample holds
        the cell's number of served tokens, all within the reference's
        packed length."""
        spec = self.cell.spec["check"]
        done = sorted((r for r in self.served.values() if r.finished),
                      key=lambda r: r.uid)
        if not done:
            return []
        rng = np.random.default_rng(self.seed)
        order = [done[j] for j in rng.permutation(len(done))]
        longest = max(done, key=lambda r: (len(r.tokens), r.prompt_len))
        picked = [longest]
        for t in self.tier_names:
            cand = [r for r in order if r.tier == t and r not in picked]
            if cand and all(r.tier != t for r in picked):
                picked.append(cand[0])
        for r in order:
            if sum(len(p.tokens) for p in picked) >= spec["served_tokens"]:
                break
            if r not in picked:
                picked.append(r)
        kept: List[stats.Served] = []
        for r in picked:
            size = sum(q.prompt_len + q.max_new - 1 for q in kept)
            if size + r.prompt_len + r.max_new - 1 <= spec["packed_tokens"]:
                kept.append(r)
        return kept

    def check(self) -> Dict[str, Any]:
        """The widest gap by which a served token's logit lies below the
        reference's best at its position, over every sampled token of every
        tier, against its limit."""
        ref = load_module("references", self.cfg["reference"])
        picked = self.sample()
        if not picked:
            log("check: no finished request to compare")
            return {}
        seqs = [{"tokens": np.concatenate(
            [self.prompts[r.uid], np.asarray(r.tokens[:-1], np.int32)]),
            "tier": r.tier, "score_from": r.prompt_len - 1} for r in picked]
        logits = np.asarray(ref.logits(self.cfg, self.seed, seqs))
        targets = np.concatenate([np.asarray(r.tokens) for r in picked])
        row_tier = np.concatenate([[r.tier] * len(r.tokens) for r in picked])
        gaps = token_gaps(logits, targets)
        best = logits.argmax(axis=1)
        for t in self.tier_names:
            m = row_tier == t
            if m.any():
                g = gaps[m]
                log(f"check {t}: {len(g)} tokens, gap max {g.max():.6g} p90 "
                    f"{np.percentile(g, 90):.6g} median {np.median(g):.6g}, "
                    f"served token is the reference's best at "
                    f"{(best[m] == targets[m]).mean():.4f}")
        log(f"check: {len(picked)} requests, {len(targets)} served tokens, "
            f"{sum(len(q['tokens']) for q in seqs)} reference positions")
        limit = self.cell.spec["check"]["limits"]["gap"]
        return {"gap": {"value": float(gaps.max()), "limit": limit}}


@contextlib.contextmanager
def _null_span(name: str) -> Any:
    yield
