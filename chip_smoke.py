#!/usr/bin/env python3
"""Serve granite-3-8b at its published widths on one TPU chip, end to end.

    python chip_smoke.py              # one chip: serve, reference, packed
    python chip_smoke.py --mesh4      # four chips: tensor-parallel serving
    python chip_smoke.py --kv-identity  # one chip: mixed vs homogeneous KV

The model is granite-3-8b (d_model 4096, 32/8 heads, head_dim 128, d_ff
12800, vocab 49155) with its depth cut to ``LAYERS`` of 40 layers and
seeded random weights.  Everything runs in this one process, which holds
the chip, through the same construction path as ``repro.launch.serve``
(``LM.init`` -> ``prepare_params`` -> ``Runtime`` -> ``ServeEngine``).

One chip, three phases over the same eight requests (prompts of 16 to 600
tokens, 33 tokens each, tiers 8/8, 4/4 and 2/2 round-robin with KV tiers
bf16, 8 and 4 in one mixed arena, so every decode batch is mixed-tier):

  serve      ``backend="pallas"`` on the unpacked superplane store;
  reference  ``backend="decomposed"`` (plain-HLO GEMMs) on the SAME store;
             its tokens and its prefill last-position logits (the model's
             forward under each runtime) must equal the serve phase's bit
             for bit;
  packed     ``backend="pallas"`` on the packed store; its tokens must equal
             the serve phase's.

``--kv-identity`` runs only the KV-arena check, on the packed store: the
requests at ``KV_IDENTITY_NEW`` tokens each through the mixed KV arena,
then through an engine whose whole arena is homogeneous at one tier's KV
precision (bf16 for 8/8, int4 for 2/2); that tier's token streams must be
identical.  Decode attention reads every encoding through one kernel on
the chip, so this holds there as it does on the CPU's jnp path.

``--mesh4`` runs only the tensor-parallel path: the same model unsharded on
one chip, then ``ServeEngine(mesh=make_serve_mesh(4))`` on the same
requests; the token streams must be identical.

The script fails (non-zero exit, no result line) when JAX finds no TPU or
any phase fails.  Its last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The seconds it prints are walls of one smoke run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from typing import Any, Dict, List, Sequence

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

LAYERS = 8                          # of granite-3-8b's 40
TIERS = {"8/8": (8, 8), "4/4": (4, 4), "2/2": (2, 2)}
KV_TIERS = {"8/8": None, "4/4": 8, "2/2": 4}
PROMPT_LENS = (600, 16, 130, 257, 64, 420, 200, 520)
MAX_NEW = 33                        # one prefill token + 4 chunks of 8
MAX_BATCH = 8
MAX_LEN = 1024
DECODE_CHUNK = 8
PROMPT_BUCKET = 640                 # one prefill program per tier
KV_IDENTITY_TIERS = ("8/8", "2/2")  # bf16 and int4 KV
KV_IDENTITY_NEW = 129               # 387 tokens at 8/8, 258 at 2/2

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (a persistent
    cache read counts as the compile it replaces), and the persistent
    cache's hits, from ``jax.monitoring``."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float,
                  **_: Any) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += duration_secs

    def _event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclasses.dataclass
class Smoke:
    """One smoke run's model, requests and reporting."""

    cfg: Any
    prompt_lens: Sequence[int] = PROMPT_LENS
    max_new: int = MAX_NEW
    max_batch: int = MAX_BATCH
    max_len: int = MAX_LEN
    prompt_bucket: int = PROMPT_BUCKET
    seed: int = 0

    def __post_init__(self) -> None:
        from repro.models.transformer import LM
        self.model = LM(self.cfg)
        self.clock = CompileClock()

    def schedule(self, backend: str, mixed_kv: bool = True) -> Any:
        from repro.core.policy import uniform_schedule
        return uniform_schedule(TIERS, backend=backend,
                                kv_tiers=KV_TIERS if mixed_kv else None)

    def init_params(self) -> Any:
        """Seeded float weights, made on the device."""
        return jax.jit(self.model.init)(jax.random.PRNGKey(self.seed))

    def requests(self) -> List[Any]:
        from repro.serve import Request
        rng = np.random.default_rng(self.seed)
        names = list(TIERS)
        return [Request(uid=i, prompt=rng.integers(0, self.cfg.vocab_size,
                                                   size=n),
                        max_new_tokens=self.max_new,
                        tier=names[i % len(names)])
                for i, n in enumerate(self.prompt_lens)]

    def engine(self, params: Any, backend: str, mixed_kv: bool = True,
               **kw: Any) -> Any:
        from repro.launch.serve import build_engine
        sched = self.schedule(backend, mixed_kv)
        return build_engine(self.model, params, policy=sched.policy_for(),
                            schedule=sched, max_batch=self.max_batch,
                            max_len=self.max_len, decode_chunk=DECODE_CHUNK,
                            prompt_bucket=self.prompt_bucket, **kw)

    def serve(self, name: str, engine: Any) -> Dict[int, List[int]]:
        """Serve every request through submit/step/drain; report the
        phase's compile seconds, wall and peak device bytes."""
        c0, h0, t0 = self.clock.seconds, self.clock.cache_hits, time.time()
        reqs = self.requests()
        handles = [engine.submit(r) for r in reqs]
        while engine.has_work:
            engine.step()
        out = {h.uid: list(h.tokens) for h in handles}
        assert all(len(t) == r.max_new_tokens
                   for t, r in zip(out.values(), reqs)), out
        assert engine.stats.mixed_tier_chunks > 0, engine.stats
        self.report(name, c0, h0, t0,
                    tokens=sum(len(t) for t in out.values()),
                    mixed_chunks=engine.stats.mixed_tier_chunks)
        return out

    def last_logits(self, name: str, engine: Any, params: Any) -> Any:
        """Prefill last-position logits of every prompt: the model's
        forward under the engine's runtime, all prompts in one mixed-tier
        batch (right-padded; the model is causal)."""
        c0, h0, t0 = self.clock.seconds, self.clock.cache_hits, time.time()
        reqs = self.requests()
        names = list(TIERS)
        order = sorted(range(len(reqs)),
                       key=lambda i: (names.index(reqs[i].tier), i))
        groups = tuple((t, sum(r.tier == t for r in reqs)) for t in names
                       if any(r.tier == t for r in reqs))
        tokens = np.zeros((len(reqs), self.prompt_bucket), np.int32)
        for i, r in enumerate(reqs):
            tokens[i, :len(r.prompt)] = r.prompt
        lengths = np.asarray([len(r.prompt) for r in reqs], np.int32)
        from repro.serve.engine import serve_jit
        rt, model = engine.rt, self.model    # no device arrays in the jit

        @serve_jit                           # compiled as the engine's are
        def fwd(params: Any, tokens: Any, lengths: Any, perm: Any) -> Any:
            logits, _ = model.forward(params, rt.for_groups(groups, perm),
                                      tokens=tokens)
            idx = (lengths - 1)[:, None, None]
            return jnp.take_along_axis(logits, idx, axis=1)[:, 0]

        out = np.asarray(fwd(params, tokens, lengths,
                             np.asarray(order, np.int32)).astype(jnp.float32))
        assert np.isfinite(out).all(), f"{name}: non-finite logits"
        self.report(name, c0, h0, t0, shape=list(out.shape))
        return out

    def report(self, name: str, c0: float, h0: int, t0: float,
               **extra: Any) -> None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        line = {"phase": name, "compile_s": self.clock.seconds - c0,
                "cache_hits": self.clock.cache_hits - h0,
                "wall_s": time.time() - t0, "peak_bytes_in_use": peaks,
                **extra}
        print("smoke wall, not a benchmark: " + json.dumps(line), flush=True)


def assert_kernels_compiled(engine: Any) -> None:
    """The compiled decode program must hold the Pallas kernels as TPU
    custom calls (interpret mode would have lowered them to plain HLO)."""
    groups, perm = engine._group_layout(
        [list(TIERS)[i % len(TIERS)] for i in range(engine.max_batch)])
    b = engine.max_batch
    text = engine._decode_chunk.lower(
        engine.params, engine.arena.caches, jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.asarray(perm),
        n_steps=DECODE_CHUNK, tier=None, groups=groups,
        sampling=engine._sampling_args()).compile().as_text()
    n = text.count("tpu_custom_call")
    assert n > 0, "compiled decode program holds no Pallas kernel"
    print(f"decode program for {groups}: {n} tpu_custom_call sites")


def check_equal(name: str, got: Dict[int, List[int]],
                want: Dict[int, List[int]]) -> None:
    bad = [u for u in want if got.get(u) != want[u]]
    if bad:
        raise AssertionError(f"{name}: token streams differ for uids {bad}")
    print(f"{name}: token streams identical "
          f"({sum(len(t) for t in want.values())} tokens)")


def run_one_chip(smoke: Smoke, expect_kernels: bool = True) -> None:
    params = smoke.init_params()
    eng = smoke.engine(params, "pallas")
    del params                           # the engine holds the store now
    served = smoke.serve("serve", eng)
    if expect_kernels:
        assert_kernels_compiled(eng)
    store = eng.params
    logits = smoke.last_logits("serve_logits", eng, store)
    del eng
    # An engine's jitted closures refer back to it, so only the cycle
    # collector frees a dropped engine's arena (and, below, its store).
    gc.collect()
    ref = smoke.engine(store, "decomposed")
    check_equal("reference", smoke.serve("reference", ref), served)
    ref_logits = smoke.last_logits("reference_logits", ref, store)
    if not np.array_equal(logits, ref_logits):
        diff = float(np.abs(logits - ref_logits).max())
        raise AssertionError("prefill logits differ between the pallas and "
                             f"decomposed runtimes: max |diff| = {diff}")
    print("prefill last-position logits identical "
          f"({logits.shape[0]} prompts x {logits.shape[1]})")
    del ref, store
    gc.collect()
    params = smoke.init_params()
    packed = smoke.engine(params, "pallas", packed=True)
    del params
    check_equal("packed", smoke.serve("packed", packed), served)


def run_kv_identity(smoke: Smoke) -> None:
    params = smoke.init_params()
    mixed = smoke.engine(params, "pallas", packed=True)
    del params
    served = smoke.serve("kv_mixed", mixed)
    tiers = {r.uid: r.tier for r in smoke.requests()}
    store = mixed.params
    del mixed
    gc.collect()
    for tier in KV_IDENTITY_TIERS:
        homog = smoke.engine(store, "pallas", mixed_kv=False, packed=True,
                             kv_bits=KV_TIERS[tier])
        got = smoke.serve(f"kv_{tier}", homog)
        del homog
        gc.collect()
        keep = [u for u, t in tiers.items() if t == tier]
        check_equal(f"kv {tier} (KV {KV_TIERS[tier] or 'bf16'})",
                    {u: got[u] for u in keep}, {u: served[u] for u in keep})


def run_mesh4(smoke: Smoke) -> None:
    from repro.launch.mesh import make_serve_mesh
    params = smoke.init_params()
    eng = smoke.engine(params, "pallas")
    del params
    served = smoke.serve("unsharded", eng)
    store = eng.params
    del eng
    gc.collect()
    sharded = smoke.engine(store, "pallas", mesh=make_serve_mesh(4))
    del store
    gc.collect()
    check_equal("mesh4", smoke.serve("mesh4", sharded), served)
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        print(f"device {d.id}: bytes_in_use={st.get('bytes_in_use')} "
              f"peak_bytes_in_use={st.get('peak_bytes_in_use')}")


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh4", action="store_true",
                    help="run only the 4-chip tensor-parallel path")
    ap.add_argument("--kv-identity", action="store_true",
                    help="run only the mixed-vs-homogeneous KV check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    need = 4 if args.mesh4 else 1
    if len(devices) < need:
        print(f"chip_smoke: --mesh4 needs 4 chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    cfg = dataclasses.replace(get_config("granite-3-8b"), num_layers=LAYERS)
    print(f"device: {devices[0].device_kind} x {len(devices)}; "
          f"compile cache: {cache}")
    print(f"model: {cfg.name} at published widths (d_model {cfg.d_model}, "
          f"{cfg.num_heads}/{cfg.num_kv_heads} heads, head_dim "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}); "
          f"depth cut to {LAYERS} of 40 layers; seed {args.seed}")
    t0 = time.time()
    smoke = Smoke(cfg, seed=args.seed,
                  max_new=KV_IDENTITY_NEW if args.kv_identity else MAX_NEW)
    if args.mesh4:
        run_mesh4(smoke)
    elif args.kv_identity:
        run_kv_identity(smoke)
    else:
        run_one_chip(smoke)
    print(f"smoke wall, not a benchmark: total {time.time() - t0:.1f}s, "
          f"compile {smoke.clock.seconds:.1f}s, "
          f"{smoke.clock.cache_hits} compile-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
