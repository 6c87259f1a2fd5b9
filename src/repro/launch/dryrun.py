import os
os.environ["JAX_PLATFORMS"] = "cpu"      # placeholder devices are CPU ones
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count="
    + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
    + " " + os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on the
production mesh with placeholder host devices; record memory/cost/collective
analysis for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

The environment lines above MUST stay the first statements — jax locks the
platform and device count on first init.  They are process-local: smoke
tests and benches never import this module.
"""
import argparse
import dataclasses
import gzip
import json
import re
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced_config
from repro.core.policy import uniform_policy
from repro.launch import hlo_cost
from repro.distributed import sharding_rules as rules
from repro.launch import specs as specs_mod
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models.layers import Runtime
from repro.models.transformer import LM
from repro.serve.engine import prepare_params
from repro.train import optimizer as optim
from repro.train.step import make_serve_steps, make_train_step

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _nbytes(dtype: str, dims: str) -> int:
    n = _DTYPE_BYTES.get(dtype, 4)
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return max(len([t for t in m.group(1).split(",") if t.strip()]), 1)
    return 1


def parse_collectives(hlo_text: str) -> Dict[str, Any]:
    """Sum *operand* bytes of every collective op in compiled HLO text.

    Operands are printed untyped (%name), so operand bytes are derived from
    the result type(s): all-gather operand = result/group, reduce-scatter
    operand = result*group, others operand = result."""
    per_op: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    counts: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        hit = None
        for op in _COLLECTIVES:
            idx = line.find(f" {op}(")
            if idx < 0:
                idx = line.find(f" {op}-start(")
            if idx >= 0:
                hit = (op, idx)
                break
        if hit is None:
            continue
        op, idx = hit
        eq = line.find(" = ")
        if eq < 0 or eq > idx:
            continue
        result_seg = line[eq + 3: idx]
        rbytes = sum(_nbytes(m.group(1), m.group(2))
                     for m in _SHAPE_RE.finditer(result_seg))
        g = _group_size(line)
        if op == "all-gather":
            obytes = rbytes // g
        elif op == "reduce-scatter":
            obytes = rbytes * g
        else:
            obytes = rbytes
        per_op[op] += obytes
        counts[op] += 1
    return {"bytes_per_op": per_op,
            "counts": counts,
            "total_bytes": sum(per_op.values())}


def _mem_dict(compiled) -> Dict[str, Any]:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover - backend specific
        return {"error": str(e)}
    out = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, f, None)
        if v is not None:
            out[f] = int(v)
    if not out:
        out["repr"] = str(ma)
    return out


def build_cell(arch: str, shape_name: str, *, multi_pod: bool,
               backend: Optional[str], w_bits: int, a_bits: int,
               kv_bits: Optional[int], reduced: bool,
               moment_dtype: str = "bfloat16", packed: bool = False,
               accum: int = 1):
    """Returns (lowered, meta) or (None, skip_reason)."""
    cfg = reduced_config(arch) if reduced else get_config(arch)
    shape = specs_mod.SHAPES[shape_name]
    if reduced:
        shape = dataclasses.replace(
            shape, seq_len=min(shape.seq_len, 128),
            global_batch=min(shape.global_batch, 8))
    ok, reason = specs_mod.cell_applicable(cfg, shape)
    if not ok:
        return None, reason
    model = LM(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod) if not reduced else \
        make_mesh((2, 2), ("data", "model"))

    with mesh:
        if shape.kind == "train":
            be = backend or "fake_quant"
            rt = Runtime(policy=uniform_policy(w_bits, a_bits, backend=be))
            ocfg = optim.OptConfig(moment_dtype=moment_dtype)
            train_step = make_train_step(model, rt, ocfg, accum_steps=accum)
            p_shapes = jax.eval_shape(model.init, jax.random.key(0))
            o_shapes = jax.eval_shape(lambda p: optim.init_state(p, ocfg),
                                      p_shapes)
            state_shapes = {"params": p_shapes, "opt": o_shapes}
            state_sh = {"params": rules.tree_shardings(mesh, p_shapes),
                        "opt": rules.tree_shardings(mesh, o_shapes)}
            batch_shapes = specs_mod.batch_specs(cfg, shape)
            batch_sh = rules.batch_shardings(mesh, batch_shapes)
            fn = jax.jit(train_step,
                         in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,))
            lowered = fn.lower(state_shapes, batch_shapes)
        else:
            be = backend or "decomposed"
            rt = Runtime(policy=uniform_policy(w_bits, a_bits, backend=be),
                         mode="serve")
            prefill_fn, decode_fn = make_serve_steps(model, rt)
            p_shapes = jax.eval_shape(model.init, jax.random.key(0))
            if be in ("decomposed", "pallas"):
                # Offline weight preparation: planes preloaded like the array.
                p_shapes = jax.eval_shape(
                    lambda p: prepare_params(p, rt.policy, model,
                                             packed=packed)[0], p_shapes)
            p_sh = rules.tree_shardings(mesh, p_shapes)
            b = shape.global_batch
            c_shapes = jax.eval_shape(
                lambda: model.init_cache(b, shape.seq_len, kv_bits=kv_bits))
            c_sh = rules.cache_shardings(mesh, c_shapes)
            if shape.kind == "prefill":
                tok = specs_mod.token_specs(cfg, b, shape.seq_len)
                tok_sh = rules.batch_shardings(mesh, tok)
                fn = jax.jit(
                    lambda p, c, t: prefill_fn(p, c, **t),
                    in_shardings=(p_sh, c_sh, tok_sh),
                    out_shardings=(None, c_sh),
                    donate_argnums=(1,))
                lowered = fn.lower(p_shapes, c_shapes, tok)
            else:
                tok = specs_mod.token_specs(cfg, b, 1)
                tok_sh = rules.batch_shardings(mesh, tok)
                fn = jax.jit(
                    lambda p, c, t: decode_fn(p, c, **t),
                    in_shardings=(p_sh, c_sh, tok_sh),
                    out_shardings=(None, c_sh),
                    donate_argnums=(1,))
                lowered = fn.lower(p_shapes, c_shapes, tok)

        meta = {
            "arch": cfg.name, "family": cfg.family, "shape": shape.name,
            "kind": shape.kind, "seq_len": shape.seq_len,
            "global_batch": shape.global_batch,
            "mesh": "x".join(str(s) for s in mesh.devices.shape),
            "axes": list(mesh.axis_names),
            "n_devices": int(mesh.devices.size),
            "backend": be, "w_bits": w_bits, "a_bits": a_bits,
            "kv_bits": kv_bits, "packed": packed, "accum": accum,
            "param_count": cfg.param_count(),
            "active_param_count": cfg.active_param_count(),
            "model_flops": specs_mod.model_flops(cfg, shape),
        }
        return (lowered, mesh), meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             backend: Optional[str] = None, w_bits: int = 4, a_bits: int = 8,
             kv_bits: Optional[int] = None, reduced: bool = False,
             dump_hlo: Optional[str] = None,
             packed: bool = False, accum: int = 1) -> Dict[str, Any]:
    t0 = time.time()
    built, meta = build_cell(arch, shape_name, multi_pod=multi_pod,
                             backend=backend, w_bits=w_bits, a_bits=a_bits,
                             kv_bits=kv_bits, reduced=reduced, packed=packed,
                             accum=accum)
    if built is None:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "skipped": True, "reason": meta}
    lowered, mesh = built
    t_lower = time.time() - t0
    with mesh:
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = _mem_dict(compiled)
    print("memory_analysis:", json.dumps(mem))          # proves it fits
    try:
        cost = dict(compiled.cost_analysis())
    except Exception as e:
        cost = {"error": str(e)}
    print("cost_analysis: flops=%s bytes=%s" % (
        cost.get("flops"), cost.get("bytes accessed")))

    hlo = compiled.as_text()
    # Loop-aware re-analysis: cost_analysis counts while bodies once; the
    # hlo_cost walker multiplies by trip counts (see launch/hlo_cost.py).
    loop_aware = hlo_cost.analyze(hlo)
    coll = loop_aware["collectives"]
    if dump_hlo:
        with gzip.open(dump_hlo, "wt") as f:
            f.write(hlo)

    res = dict(meta)
    res.update({
        "skipped": False,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops": loop_aware["flops"],
        "bytes_accessed": loop_aware["bytes"],
        "xla_cost_raw": {k: v for k, v in cost.items()
                         if isinstance(v, (int, float)) and
                         k in ("flops", "bytes accessed", "transcendentals")},
        "collectives": coll,
        "collectives_unscaled": parse_collectives(hlo),
        "memory": mem,
        "hlo_lines": hlo.count("\n"),
    })
    return res


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True,
                    choices=sorted(specs_mod.SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--backend", default=None,
                    choices=["dense", "fake_quant", "decomposed", "pallas"])
    ap.add_argument("--w-bits", type=int, default=4)
    ap.add_argument("--a-bits", type=int, default=8)
    ap.add_argument("--kv-bits", type=int, default=None)
    ap.add_argument("--packed", action="store_true",
                    help="packed plane layout (w_bits/8 bytes per weight)")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches (train cells)")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny config on a 2x2 mesh (CI / self-test)")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    ap.add_argument("--dump-hlo", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    mesh_name = ("2x16x16" if args.multi_pod else "16x16") if not args.reduced \
        else "2x2"
    stem = f"{args.arch}__{args.shape}__{mesh_name}"
    if args.backend:
        stem += f"__{args.backend}"
    if args.tag:
        stem += f"__{args.tag}"
    hlo_path = os.path.join(args.out, stem + ".hlo.gz") if args.dump_hlo else None

    res = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   backend=args.backend, w_bits=args.w_bits,
                   a_bits=args.a_bits, kv_bits=args.kv_bits,
                   reduced=args.reduced, dump_hlo=hlo_path,
                   packed=args.packed, accum=args.accum)
    out_path = os.path.join(args.out, stem + ".json")
    with open(out_path, "w") as f:
        json.dump(res, f, indent=1)
    status = "SKIP" if res.get("skipped") else "OK"
    print(f"[{status}] {stem} -> {out_path}")
    if not res.get("skipped"):
        print(f"  compile={res['compile_s']}s flops={res['flops']:.3e} "
              f"coll={res['collectives']['total_bytes']:.3e}B")


if __name__ == "__main__":
    main()
