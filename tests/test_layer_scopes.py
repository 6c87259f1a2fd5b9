"""The serving programs name the model's layers: every scope of
``repro.telemetry.xplane.LAYER_SCOPES`` lands in the ``op_name`` metadata
of the compiled decode-chunk, prefill and speculative-round programs, and
in the dense model every matmul sits under ``linear``, ``attention`` or
``lm_head`` (a profiler trace attributes device time by these names)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core.policy import uniform_schedule
from repro.models.layers import Runtime
from repro.models.transformer import LM
from repro.serve import ServeEngine
from repro.telemetry.xplane import LAYER_SCOPES, OTHER, scope_of

TIERS = {"8/8": (8, 8), "2/2": (2, 2)}
B = 3
MATMUL_SCOPES = {"linear", "attention", "lm_head"}


@pytest.fixture(scope="module")
def engine():
    cfg = reduced_config("granite-3-8b")
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = uniform_schedule(TIERS, backend="pallas",
                             kv_tiers={"8/8": None, "2/2": 4})
    rt = Runtime(policy=sched.policy_for(), mode="serve", schedule=sched)
    return ServeEngine(model, params, rt, max_batch=B, max_len=32,
                       decode_chunk=2, prompt_bucket=8, packed=True)


_CALLS = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)"
                    r"=\{?([^,}]+(?:, %[^,}]+)*)\}?")


def _ops(hlo: str):
    """(opcode, op_name) of every instruction of HLO text, with the
    op_name of an op in a called computation (a loop body, a function the
    lowering outlined, such as an interpret-mode kernel) that is relative
    to its call site prefixed with the caller's."""
    comps = {}                 # computation -> [(opcode, op_name, callees)]
    comp = None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%\S+) ", line)
        if head and line.endswith("{"):
            comp = head.group(1)
            comps[comp] = []
            continue
        m = re.search(r" = .*?\s([a-z][a-z0-9-]*)\(", line)
        if m is None or comp is None:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        callees = [c.strip() for g in _CALLS.findall(line)
                   for c in g.split(",")]
        comps[comp].append((m.group(1), name.group(1) if name else "",
                            callees))
    caller = {}
    for ops in comps.values():
        for _, name, callees in ops:
            for c in callees:
                caller.setdefault(c, name)

    def full(comp, name):
        if name.startswith("jit(") or comp not in caller:
            return name
        up = caller[comp]
        owner = next((k for k, ops in comps.items()
                      if any(n == up and comp in cs for _, n, cs in ops)),
                     None)
        return full(owner, up) + "/" + name if owner else name

    return [(op, full(c, name)) for c, ops in comps.items()
            for op, name, _ in ops]


def _programs(eng):
    groups = (("8/8", 1), ("2/2", B - 1))
    perm = jnp.arange(B, dtype=jnp.int32)
    tok = jnp.zeros((B,), jnp.int32)
    decode = eng._decode_chunk.lower(
        eng.params, eng.arena.caches, tok, tok + 3, perm, n_steps=2,
        tier=None, groups=groups, sampling=eng._sampling_args())
    prefill = eng._prefill_slot.lower(
        eng.params, eng.arena.caches, jnp.int32(0),
        jnp.zeros((1, 8), jnp.int32), jnp.int32(5), jnp.int32(16),
        jnp.asarray(eng._key[0]), jnp.float32(0.0), jnp.int32(0),
        tier="8/8")
    spec = eng._spec_round.lower(
        eng.params, eng.arena.caches, tok, tok + 3, perm, perm,
        jnp.asarray(np.array([True] + [False] * (B - 1))),
        eng._sampling_args(), k=2, draft_groups=(("2/2", B),),
        verify_groups=groups)
    return {"decode": decode, "prefill": prefill, "spec": spec}


@pytest.fixture(scope="module")
def compiled(engine):
    """Each program's HLO as lowered: what the program states, before the
    compiler's own rewrites (which may add ops without metadata)."""
    from jax._src.lib import xla_client
    opts = xla_client._xla.HloPrintOptions()
    opts.print_metadata = True
    return {k: v.compiler_ir("hlo").get_hlo_module().to_string(opts)
            for k, v in _programs(engine).items()}


@pytest.mark.parametrize("program,scopes", [
    ("decode", {"linear", "kv_write", "attention", "lm_head", "sample"}),
    ("prefill", set(LAYER_SCOPES)),
    ("spec", {"linear", "kv_write", "attention", "lm_head", "sample"}),
])
def test_programs_carry_the_layer_scopes(compiled, program, scopes):
    found = {scope_of(name) for _, name in _ops(compiled[program])}
    assert scopes <= found, scopes - found


@pytest.mark.parametrize("program", ["decode", "prefill", "spec"])
def test_every_matmul_sits_in_a_matmul_scope(compiled, program):
    """Dots and kernel calls (Pallas runs in interpret mode here, so its
    dots are the kernel's) belong to a layer that multiplies."""
    matmuls = [(op, name) for op, name in _ops(compiled[program])
               if op in ("dot", "custom-call")]
    assert matmuls
    stray = [(op, name) for op, name in matmuls
             if scope_of(name) not in MATMUL_SCOPES]
    assert not stray, stray[:5]


def test_scope_of_takes_the_outermost():
    assert scope_of("jit(f)/while/body/lm_head/linear/dot_general") \
        == "lm_head"
    assert scope_of("jit(f)/while/body/attention/kv_write/mul") \
        == "attention"
    assert scope_of("jit(f)/while/body/add") == OTHER
    assert scope_of("") == OTHER
