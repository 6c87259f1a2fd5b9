"""Slot-based continuous-batching serving engines behind the streaming API.

The paper's dataflow is "serial activation input, parallel weight
preloaded": decomposed weight planes stay resident while activations stream
through.  The engine mirrors that end to end:

* **Incremental core** — the public surface is ``submit(request) ->
  RequestHandle`` / ``step() -> list[TokenEvent]`` / ``drain()``:
  requests enter any time, every scheduling round returns the tokens it
  emitted, and handles stream them (iterator + callback) as they arrive.
  ``run(requests)`` is a thin compatibility wrapper (submit all, drain,
  collect) and is token-identical to the historical blocking API.
* **Weight preload** — at construction the float params are converted ONCE
  into the ``QuantizedWeight`` plane pytree (``prepare_params``); that
  prepared pytree is the engine's only weight representation.
* **Runtime precision tiers** — with a ``PrecisionSchedule`` on the
  Runtime, the preload is a single 8-bit MSB-first *superplane* store and
  every decode dispatch picks effective (w_bits, a_bits) tiers by
  plane-prefix truncation.  Switching tiers costs zero weight
  re-preparation (``PREPARE_CALLS`` counts preparations — it must not move
  after construction).
* **Mixed-tier decode batches** — slots are tier-tagged: admission fills
  ANY free slot, and each decode chunk derives a per-step group layout from
  the occupied slots' tiers — a jit-STATIC tuple of ``(tier, rows)`` sorted
  by tier, plus a TRACED permutation mapping batch rows into that order.
  Every projection then runs one plane-prefix GEMM per group, so one jitted
  decode step serves slots at 8/6/4/2 bits simultaneously (see
  ``models.layers.linear``).  ``mixed_tiers=False`` keeps the PR-2
  tier-serialized admission (one tier per decode batch) as the baseline.
* **Mid-stream tier migration** — ``RequestHandle.set_tier(name)`` moves a
  LIVE request to another tier: the slot's KV lane is requantized in place
  (``slots.migrate_kv_tier`` — one jitted dequantize/re-encode through the
  nested-quantization path, bit-identical to quantizing the dequantized
  cache directly at the target precision) and the weight plane prefix
  switches at the next group-layout derivation.  QUEUED requests are
  simply re-tagged.
* **Pluggable admission** — WHICH waiting request takes a freed slot is a
  ``SchedulerPolicy``: ``FIFOPolicy`` (default, bit-identical to the
  historical behaviour) or ``SLOPolicy`` (deadline slack vs. the hwmodel's
  per-tier cycle cost; see ``serve/scheduler.py``).
* **Overload survival** — ``SLOPolicy`` extensions turn admission into
  overload control: ``preempt=True`` displaces the slackest RUNNING slot
  when a deadlined waiting request runs out of slack (``Engine.preempt``
  snapshots the slot's KV/SSM slice + host decode state into a
  ``SuspendedState`` — optionally spilled through ``repro.checkpoint`` —
  and the request later resumes prefill-free, token-identical, in ANY
  slot); ``shed=True`` refuses (or, with ``auto_tier``, downtiers)
  deadline requests whose projected completion exceeds modeled capacity;
  ``tenant_weights`` ages weighted tenants' queued requests faster so one
  tenant's burst cannot starve another's.  ``Engine.cancel`` aborts
  QUEUED/SUSPENDED requests without leaking scheduler state.
* **Per-request KV precision** — a schedule with ``kv_tiers`` allocates one
  mixed per-slot KV arena: each admitted request's slot stores K/V at its
  tier's precision (bf16 / int8 / int4-packed lanes, per-slot scale rows).
* **Persistent decode state** — a fixed-slot cache arena
  (:mod:`repro.serve.slots`): per-slot KV lengths and SSM states live in one
  pre-allocated pytree across the whole request stream.
* **On-device decode loop** — the inner loop is ONE jitted multi-step
  ``jax.lax.scan`` over a chunk of decode steps with an active-slot mask and
  masked cache writes; the host only admits/retires requests between
  chunks, so per-token dispatch overhead is off the critical path.

Jit-static vs traced (the contract everything above hangs on): tier names,
group layouts, chunk lengths and prompt buckets are STATIC (they key
traces: at most |layouts| x decode_chunk decode entries); slot indices,
token ids, budgets, the group permutation, per-slot KV tier codes and the
migration target code are TRACED (they change every step/migration without
retracing).

The scheduler clock: every engine counts decode steps executed
(``Engine.clock``); submission times, queue waits and ``Request.deadline``
are priced in these ticks, keeping SLO admission fully deterministic.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import (Any, Dict, List, Mapping, Optional, Protocol, Sequence,
                    Set, Tuple, runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np
import numpy.typing as npt
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import checkpoint as checkpoint_lib
from repro.core.policy import PrecisionPolicy, format_group_layout
from repro.distributed import sharding_rules, tp_serve
from repro.kernels import kv_attention, ops
from repro.models.layers import Runtime, decode_kernel_engages
from repro.models.transformer import LM
from repro.serve import slots as slots_lib
from repro.serve.handle import RequestHandle, RequestStatus, TokenEvent
from repro.serve.request import Request
from repro.serve.scheduler import (RunningEntry, Scheduler, SchedulerPolicy,
                                   SLOPolicy)
from repro.spec import sampling as sampling_lib
from repro.spec import speculate as spec_lib

__all__ = ["Request", "RequestHandle", "RequestStatus", "TokenEvent",
           "Engine", "ServeEngine", "BatchServeEngine", "EngineStats",
           "SuspendedState", "prepare_params", "PREPARE_CALLS"]

# Mixed-tier group layout: the jit-STATIC tuple of (tier name, rows) runs
# describing a tier-sorted decode batch (see Runtime.for_groups).
GroupLayout = Tuple[Tuple[str, int], ...]

# Global weight-preparation counter: every prepare_params call (one quantize+
# decompose sweep over the params) bumps it.  The runtime-tier contract —
# zero re-preparation after engine construction — is asserted against this
# in tests and the serve_precision_tiers / serve_mixed_tiers benchmarks.
PREPARE_CALLS = 0

# Serving programs compile with the bf16 roundings they state.  By default
# XLA may keep a bf16 intermediate in f32 across a fusion ("excess
# precision"), and which ones it keeps depends on how the program fuses.
# Pallas kernels are fusion barriers, so the pallas and decomposed backends
# would round in different places, and on a TPU v5e their tokens diverged.
COMPILER_OPTIONS = {"xla_allow_excess_precision": False}
serve_jit = functools.partial(jax.jit, compiler_options=COMPILER_OPTIONS)

# The engine's host phases as profiler spans (``serve.step``,
# ``serve.decode``, ``serve.wait_decode``, ...; docs/observability.md): they
# land in a ``jax.profiler`` trace on the clock of the device ops, and with
# no profiler session running each costs a flag check.
span = jax.profiler.TraceAnnotation


def prepare_params(params: Any, policy: PrecisionPolicy, model: LM,
                   packed: bool = False,
                   superplane: bool = False) -> Tuple[Any, List[str]]:
    """Quantize + decompose every policy-covered projection weight offline.

    Returns a params pytree where 2D projection weights are replaced by
    QuantizedWeight planes (embeddings/norms stay dense).  ``superplane``
    prepares the runtime-reconfigurable store instead: 8-bit MSB-first
    planes regardless of the policy's per-layer w_bits (which then acts per
    decode dispatch via plane-prefix truncation)."""
    global PREPARE_CALLS
    PREPARE_CALLS += 1

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    quantized_paths: List[str] = []
    for kp, leaf in flat:
        path = jax.tree_util.keystr(kp)
        is_proj = path.endswith("['w']") and leaf.ndim >= 2 \
            and "embed" not in path and "router" not in path \
            and "conv" not in path
        if is_proj:
            prec = policy.lookup(_path_to_layer_name(path))
            out.append(_prepare_leaf(leaf, prec, packed=packed,
                                     superplane=superplane))
            quantized_paths.append(path)
            continue
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out), quantized_paths


@functools.partial(jax.jit, static_argnames=("prec", "packed", "superplane"))
def _prepare_leaf(leaf: jax.Array, prec: Any, *, packed: bool,
                  superplane: bool) -> ops.QuantizedWeight:
    """Prepare one projection weight ([..., K, N]; leading period / expert
    dims are mapped one slice at a time).  One jitted program per weight
    shape: run op by op, the quantize + decompose chain would hold several
    int32 copies of a full-width weight on the device at once."""
    def prep(w: jax.Array) -> ops.QuantizedWeight:
        w = w.astype(jnp.float32)
        if superplane:
            return ops.prepare_superplane(w, signed=prec.w_signed,
                                          packed=packed)
        return ops.prepare_weight(w, prec, packed=packed)

    if leaf.ndim == 2:
        return prep(leaf)
    lead = leaf.shape[:-2]
    qws = jax.lax.map(prep, leaf.reshape((-1,) + leaf.shape[-2:]))
    out: ops.QuantizedWeight = jax.tree.map(
        lambda a: a.reshape(lead + a.shape[1:]), qws)
    return out


def _path_to_layer_name(path: str) -> str:
    # "['periods']['pos0']['attn']['q_proj']['w']" -> "layers.pos0.attn.q_proj"
    parts = [p.strip("'") for p in path.strip("[]").split("][")]
    if parts and parts[0] == "periods":
        parts = ["layers"] + parts[1:]
    if parts and parts[-1] == "w":
        parts = parts[:-1]
    return ".".join(parts)


def _validate_request(request: Request, max_len: int,
                      seen_uids: Set[int]) -> None:
    """The admission contract both engines share (one place to change):
    non-empty prompt, positive decode budget, fits the arena, fresh uid."""
    plen = len(request.prompt)
    if plen == 0:
        raise ValueError(f"request {request.uid}: empty prompt")
    if request.max_new_tokens < 1:
        raise ValueError(f"request {request.uid}: max_new_tokens must be "
                         f">= 1, got {request.max_new_tokens}")
    if plen + request.max_new_tokens > max_len:
        raise ValueError(
            f"request {request.uid}: prompt ({plen}) + max_new_tokens "
            f"({request.max_new_tokens}) exceeds max_len {max_len}")
    if request.uid in seen_uids:
        raise ValueError(f"request uid {request.uid} already submitted "
                         "(results are keyed by uid)")


def params_prepared(params: Any) -> bool:
    """True once ``params`` holds prepared (QuantizedWeight) projections."""
    return any(isinstance(l, ops.QuantizedWeight) for l in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, ops.QuantizedWeight)))


def _ensure_prepared(params: Any, rt: Runtime, model: LM,
                     packed: bool) -> Tuple[Any, List[str]]:
    """Weight preload shared by both engines: prepare the plane pytree once
    at construction unless the caller already did.  Returns (params, paths
    of QuantizedWeight leaves).  A Runtime carrying a PrecisionSchedule gets
    the superplane store (one 8-bit preload serving every tier)."""
    if rt.schedule is not None:
        if not params_prepared(params):
            return prepare_params(params, rt.schedule.prepare_policy(), model,
                                  packed=packed, superplane=True)
    else:
        backend = rt.policy.default.backend
        if backend in ("decomposed", "pallas") and not params_prepared(params):
            return prepare_params(params, rt.policy, model, packed=packed)
    paths = [jax.tree_util.keystr(kp) for kp, l in
             jax.tree_util.tree_flatten_with_path(
                 params, is_leaf=lambda x: isinstance(
                     x, ops.QuantizedWeight))[0]
             if isinstance(l, ops.QuantizedWeight)]
    return params, paths


@dataclasses.dataclass
class EngineStats:
    """Work accounting (the utilization story of the refactor).

    Tier accounting under mixed-tier batches: a decode step that serves
    several tiers at once counts its ``n_steps`` toward EVERY tier with an
    occupied slot (``decode_steps_by_tier``), while ``tokens_by_tier``
    counts only each tier's own active slot-steps.  ``tier_switches`` only
    moves in tier-serialized mode (mixed batches never switch);
    ``mixed_tier_chunks`` counts dispatches whose batch held >= 2 tiers.
    ``tier_migrations`` counts successful mid-stream ``set_tier`` calls on
    RUNNING requests; ``kv_migrations`` counts the subset that requantized
    a live KV lane (the tiers mapped to different KV precisions).

    Overload-control accounting: ``preemptions`` counts RUNNING slots
    suspended (snapshot + evict), ``resumes`` the prefill-free
    re-admissions of suspended requests (equal once the engine drains —
    every suspension either resumes or is cancelled), ``sheds`` the
    requests refused by admission control or cancelled by the caller, and
    ``spill_bytes`` the snapshot bytes persisted through the checkpoint
    spill path (0 when suspensions stay host-resident).
    ``time_slice_preemptions`` counts the voluntary yields of best-effort
    slots under ``SLOPolicy(time_slice=N)``.

    Speculative-decoding accounting (``Request.spec``): a round of draft
    depth k counts ``k`` draft-tier decode steps (``spec_draft_steps``)
    plus ONE verify window forward (``spec_verify_steps``) — both also
    roll into ``decode_steps`` (k+1 clock ticks per round).
    ``spec_drafted`` counts proposed draft tokens, ``spec_accepted`` the
    drafts that survived verification, and ``spec_emitted`` every token a
    speculative round emitted (accepted drafts + correction/bonus
    tokens), so ``spec_accepted / spec_drafted`` is the acceptance rate
    and ``spec_verify_steps / spec_emitted`` the verify-tier steps per
    emitted token (< 1 iff speculation beats plain decoding).

    KV read accounting (plain decode chunks; speculative rounds are not
    counted): ``kv_positions_read`` sums, over decode steps and slots, the
    KV positions decode attention fetches per layer — whole blocks up to
    each slot's fill point when the decode kernel engages
    (``layers.decode_kernel_engages``), the whole ``max_len`` otherwise —
    and ``kv_positions_reserved`` adds ``max_batch * max_len`` per decode
    step, so their ratio is the share of the arena decode reads (1.0 on
    the jnp path).  Both are computed on the host from the mirrored fill
    points (no device sync) and stay 0 for a model without attention."""

    prefills: int = 0
    prefill_tokens: int = 0        # real (unpadded) prompt tokens prefilled
    decode_steps: int = 0          # jitted model decode steps executed
    decode_chunks: int = 0         # jitted multi-step calls dispatched
    decode_slot_steps: int = 0     # sum over steps of active slots (useful)
    decode_idle_slot_steps: int = 0  # masked-out slot-steps (waste bound)
    tier_switches: int = 0         # decode-phase precision changes (serialized)
    mixed_tier_chunks: int = 0     # chunks serving >= 2 tiers in one batch
    tier_migrations: int = 0       # mid-stream set_tier on RUNNING requests
    kv_migrations: int = 0         # ... of which requantized a live KV lane
    tier_autoselects: int = 0      # deadline-driven admission-time retags
    preemptions: int = 0           # RUNNING slots suspended (snapshot+evict)
    resumes: int = 0               # prefill-free re-admissions of suspensions
    sheds: int = 0                 # admission-control refusals + cancels
    spill_bytes: int = 0           # snapshot bytes persisted via checkpoint
    time_slice_preemptions: int = 0  # voluntary best-effort time-slice yields
    spec_rounds: int = 0           # speculative rounds dispatched
    spec_draft_steps: int = 0      # draft-tier decode steps (k per round)
    spec_verify_steps: int = 0     # verify window forwards (1 per round)
    spec_drafted: int = 0          # draft tokens proposed (k per spec slot)
    spec_accepted: int = 0         # drafts accepted by verification
    spec_emitted: int = 0          # tokens emitted by speculative rounds
    layout_cache_hits: int = 0     # group-layout derivations skipped (cache)
    layout_cache_misses: int = 0   # group-layout derivations performed
    kv_positions_read: int = 0     # KV positions decode fetched per layer
    kv_positions_reserved: int = 0  # max_batch * max_len per decode step
    decode_steps_by_tier: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    tokens_by_tier: Dict[str, int] = dataclasses.field(default_factory=dict)
    # Dispatch-count observability (ServeEngine(count_dispatches=True)):
    # per group layout, the jaxpr ``pallas_call`` count of ONE jitted decode
    # step — with the fused grouped kernel this is CONSTANT in the number of
    # tier groups (asserted in tests/test_grouped_kernel.py).
    decode_dispatches: Dict[GroupLayout, int] = dataclasses.field(
        default_factory=dict)


@dataclasses.dataclass
class SuspendedState:
    """Host-side snapshot of one preempted request (``ServeEngine.preempt``).

    Everything a prefill-free resume needs: the request, the tokens already
    emitted, the decode budget still owed, the last emitted token (the next
    decode step's input), and the slot's batch-1 cache pytree — KV lanes
    (with their per-slot tier codes and lengths), scale rows, and SSM state
    — exactly as :func:`repro.serve.slots.slot_view` cut it from the arena.
    The snapshot is slot-agnostic: resume may write it into ANY free slot.

    ``cache`` holds the host (numpy) pytree, or None once the snapshot was
    spilled to disk through :mod:`repro.checkpoint` (``spill_step`` then
    names the checkpoint step under the engine's ``spill_dir``).
    ``nbytes`` is the snapshot's byte footprint either way.  ``draws`` is
    the slot's sampling draw counter at suspension — the resumed stream
    continues the request's private PRNG stream exactly where it stopped
    (token-identical to the uninterrupted sampled run)."""

    request: Request
    tokens: List[int]
    remaining: int
    last_token: int
    cache: Optional[Any]
    spill_step: Optional[int] = None
    nbytes: int = 0
    draws: int = 0


class _DeferredErrors:
    """Shared callback-error deferral: a raising user ``on_token`` callback
    must not abort a scheduling round midway (that would desync host slot
    bookkeeping from the already-advanced device state).  Engines route
    callback exceptions here (``RequestHandle._push(defer=...)``) and
    re-raise the FIRST one at the end of the round via
    :meth:`_raise_deferred` — engine-internal errors are never captured
    and propagate immediately."""

    _deferred_error: Optional[BaseException] = None

    def _defer_error(self, err: BaseException) -> None:
        if self._deferred_error is None:
            self._deferred_error = err

    def _raise_deferred(self) -> None:
        """Re-raise the first callback error of the round, once the
        round's host bookkeeping is complete and consistent."""
        if self._deferred_error is not None:
            err, self._deferred_error = self._deferred_error, None
            raise err


@runtime_checkable
class Engine(Protocol):
    """The serving surface both engines implement (see module docstring).

    ``submit`` validates + enqueues one request and returns its streaming
    handle; ``step`` runs one scheduling round and returns the tokens it
    emitted; ``drain`` steps until idle and returns every finished
    request's tokens; ``run`` is the blocking compatibility wrapper
    (submit all, drain, collect — token-identical to the historical API).
    ``clock`` is the deterministic scheduler clock (decode steps executed)
    every submission time, queue wait and ``Request.deadline`` is priced
    in.  ``cancel`` drops a request that has not finished running (QUEUED,
    or SUSPENDED on engines that preempt), flipping its handle to the
    terminal SHED state and releasing every scheduler entry it held."""

    def submit(self, request: Request) -> RequestHandle: ...

    def step(self) -> List[TokenEvent]: ...

    def drain(self) -> Dict[int, List[int]]: ...

    def run(self, requests: Sequence[Request]) -> Dict[int, List[int]]: ...

    def retire(self, uid: int) -> List[int]: ...

    def cancel(self, uid: int) -> None: ...

    @property
    def has_work(self) -> bool: ...

    @property
    def clock(self) -> float: ...


class ServeEngine(_DeferredErrors):
    """Continuous batching over ``max_batch`` persistent slots.

    Accepts a request stream (``submit`` any time; ``run`` a list for the
    blocking form); freed slots are re-prefilled individually against the
    shared cache arena while the other slots' caches stay untouched, and
    the decode inner loop is a single jitted multi-step scan
    (``decode_chunk`` steps per dispatch) with per-slot active masking.

    ``scheduler_policy`` picks WHICH waiting request takes a freed slot
    (``FIFOPolicy`` default; ``SLOPolicy`` for deadline-aware admission).

    With a ``PrecisionSchedule`` on the runtime, ``mixed_tiers`` selects the
    admission shape:

    * ``True`` (default) — tier-tagged slots: any free slot takes the
      policy's pick regardless of tier, and each decode chunk runs the
      occupied tiers TOGETHER via the per-row-group matmul path (a static
      ``(tier, rows)`` layout + a traced slot permutation, derived from
      ``SlotArena.tiers`` each step).  Only this mode supports mid-stream
      ``RequestHandle.set_tier`` on RUNNING requests.
    * ``False`` — the tier-serialized baseline: a decode batch runs at ONE
      tier and admission is restricted to matching requests (kept for the
      ``serve_mixed_tiers`` benchmark comparison).

    Constructor args that select jit behaviour (``decode_chunk``,
    ``prompt_bucket``, ``packed``, the schedule's tier/kv-mode sets) are
    static; everything that varies per request flows through traced
    arrays."""

    def __init__(self, model: LM, params: Any, rt: Runtime, *,
                 max_batch: int = 8, max_len: int = 512,
                 kv_bits: Optional[int] = None, decode_chunk: int = 8,
                 prompt_bucket: int = 8, packed: bool = False,
                 mixed_tiers: bool = True,
                 fused_decode: bool = True,
                 count_dispatches: bool = False,
                 scheduler_policy: Optional[SchedulerPolicy] = None,
                 mesh: Optional[Any] = None,
                 spill_dir: Optional[str] = None,
                 telemetry: Optional[Any] = None) -> None:
        self.model = model
        # ``fused_decode`` selects the mixed-tier grouped-matmul
        # implementation: one group-switching kernel (default) vs the
        # per-group dispatch loop (bit-identical reference).
        self.rt = dataclasses.replace(rt, fused=fused_decode)
        self.fused_decode = fused_decode
        self.count_dispatches = count_dispatches
        self.max_batch = max_batch
        self.max_len = max_len
        self.kv_bits = kv_bits
        self.decode_chunk = max(1, decode_chunk)
        self.prompt_bucket = max(1, prompt_bucket)
        self.mixed_tiers = mixed_tiers
        # Weight preload: the prepared plane pytree is the engine's ONLY
        # weight representation (prepared here unless already prepared).
        # With a PrecisionSchedule this is the 8-bit superplane store; every
        # tier below decodes against it with zero further preparation.
        self.params, self.quantized_paths = _ensure_prepared(
            params, rt, model, packed)
        self.schedule = rt.schedule
        # Tier-serialized mode only: the tier the decode batch currently
        # runs at; admission is restricted to it while any slot is occupied.
        self._active_tier: Optional[str] = None
        self._last_tier: Optional[str] = None

        # KV arena mode: a schedule with kv_tiers gets the mixed per-slot
        # arena (one byte-lane store serving every declared KV precision);
        # otherwise the engine-wide kv_bits applies to all slots.
        arena_kv: Any = kv_bits
        self._mixed_kv = False
        if self.schedule is not None and self.schedule.kv_tiers is not None:
            if kv_bits is not None:
                raise ValueError(
                    "kv_bits conflicts with the schedule's kv_tiers (per-"
                    "request KV precision); drop one of the two")
            arena_kv = self.schedule.kv_modes
            self._mixed_kv = True
        self.arena = slots_lib.SlotArena(model, max_batch, max_len,
                                         kv_bits=arena_kv)
        # Tensor-parallel serving (mesh=): shard the superplane store N-wise
        # and the KV arena over heads, validate divisibility, and place both
        # trees before any dispatch.  The jitted prefill/decode/migrate
        # functions below are then wrapped in shard_map with the quantized
        # collectives from distributed/tp_serve — token-identical to the
        # unsharded engine (the TP grouped path always runs the fused GEMM,
        # so ``fused_decode`` only affects the unsharded reference).
        self.mesh = mesh
        self._tp: Optional[tp_serve.TPConfig] = None
        if mesh is not None:
            self._tp = self._init_mesh_placement(mesh)
        self.scheduler = Scheduler(max_batch, policy=scheduler_policy)
        self.stats = EngineStats()
        # Observability (repro.telemetry.Telemetry, duck-typed so serve
        # never imports the telemetry package).  The contract: EVERY hook
        # call below is guarded by ``telemetry is not None`` and the engine
        # itself never fences — a telemetry-None engine runs the decode hot
        # loop with zero added host syncs, allocations, or hook calls.  The
        # profiler spans (``span``) are there either way.
        self.telemetry = telemetry
        if telemetry is not None:
            telemetry.attach_engine(num_slots=max_batch)
        # Group-layout memo: slot-tier vector -> (groups, perm).  Recurring
        # mixed-batch layouts (the steady state) skip the per-step Python
        # sort; hits/misses are surfaced on EngineStats.
        self._layout_cache: Dict[Tuple[Optional[str], ...],
                                 Tuple[GroupLayout,
                                       npt.NDArray[np.int32]]] = {}
        self.handles: Dict[int, RequestHandle] = {}
        self._seen_uids: Set[int] = set()
        # Preemption state: uid -> host snapshot of the suspended slot.
        # ``spill_dir`` routes snapshots through the checkpoint subsystem
        # (async atomic step dirs) instead of holding them host-resident.
        self._suspended: Dict[int, SuspendedState] = {}
        self._spill_dir = spill_dir
        self._spiller: Optional[Any] = None        # lazy AsyncCheckpointer
        self._spill_counter = 0                    # monotonic spill step ids
        self._slot_template_cache: Optional[Any] = None
        self._in_round = False                     # guards preempt() reentry
        # Host-mirrored per-slot decode state.
        self._tok: npt.NDArray[np.int32] = np.zeros((max_batch,), np.int32)
        self._remaining: npt.NDArray[np.int32] = np.zeros((max_batch,),
                                                          np.int32)
        # KV fill point of every slot (a freed slot keeps its last one)
        # and the decode kernel's engagement: the KV read accounting.
        self._kv_len: npt.NDArray[np.int64] = np.zeros((max_batch,),
                                                       np.int64)
        self._has_kv = any(m == "attn" for m, _ in model.pattern)
        self._kv_kernel = self._has_kv and decode_kernel_engages(
            max_len, *self._local_heads(), model.cfg.head_dim)
        # Per-slot sampling state (repro.spec.sampling), mirrored on host
        # and passed traced into every decode dispatch: raw request PRNG
        # keys, draw counters, temperature, top-k.  Greedy slots keep
        # temperature 0 and never advance their counter, so the sampled
        # streams are pure functions of (seed, draw index) — independent
        # of slot assignment, batch composition and chunk boundaries.
        self._key: npt.NDArray[np.uint32] = np.zeros((max_batch, 2),
                                                     np.uint32)
        self._draws: npt.NDArray[np.int32] = np.zeros((max_batch,), np.int32)
        self._temp: npt.NDArray[np.float32] = np.zeros((max_batch,),
                                                       np.float32)
        self._topk: npt.NDArray[np.int32] = np.zeros((max_batch,), np.int32)
        # Time-slice fairness bookkeeping: the scheduler-clock tick each
        # slot's CURRENT occupancy began (set at admission AND at resume).
        self._slice_start: Dict[int, float] = {}
        mixed_kv = self._mixed_kv

        def prefill_slot(params: Any, caches: Any, slot: Any, tokens: Any,
                         length: Any, kv_code: Any, key: Any, temp: Any,
                         topk: Any, tier: Optional[str] = None,
                         tp: Optional[tp_serve.TPConfig] = None
                         ) -> Tuple[Any, Any]:
            """Admit one request: reset slot, prefill its prompt (right-
            padded to a bucket), write the batch-1 cache back into the
            arena.  ``tier`` is STATIC (retraces only per prompt bucket x
            tier); ``slot``, ``tokens``, ``length``, ``kv_code`` (the
            slot's KV tier, 16/8/4) and the sampling scalars (``key``
            uint32 [2], ``temp``, ``topk`` — draw counter 0 selects the
            request's FIRST token) are traced.  ``tp`` (static) is set
            only when called inside the mesh wrapper's shard_map body."""
            rt_eff = self.rt.for_tier(tier)
            if tp is not None:
                rt_eff = dataclasses.replace(rt_eff, tp=tp)
            sub = slots_lib.slot_view(caches, slot)
            with jax.named_scope("slot_io"):  # per-slot reset
                sub = jax.tree.map(jnp.zeros_like, sub)
            if mixed_kv:
                sub = slots_lib.fill_kv_tier(sub, kv_code)
            logits, sub = self.model.prefill(
                params, rt_eff, sub, tokens=tokens,
                seq_lengths=length.reshape(1))
            caches = slots_lib.slot_write(caches, sub, slot)
            with jax.named_scope("sample"):
                tok, _ = sampling_lib.sample_tokens(
                    logits[:, -1], key[None, :], jnp.zeros((1,), jnp.int32),
                    temp.reshape(1), topk.reshape(1))
            return tok[0], caches

        def decode_chunk_fn(params: Any, caches: Any, tok: Any,
                            remaining: Any, perm: Any, n_steps: int,
                            tier: Optional[str] = None,
                            groups: Optional[GroupLayout] = None,
                            tp: Optional[tp_serve.TPConfig] = None,
                            sampling: Optional[Tuple[Any, Any, Any, Any]]
                            = None) -> Any:
            """The single jitted inner loop: ``n_steps`` decode steps as one
            lax.scan with an active mask.  A slot's budget hitting zero
            freezes its cache (masked writes) THAT step; its lane still
            flows through the matmuls (dense batch) but produces no state
            change and no emitted token.

            Precision selection — both STATIC (they key the trace):
            ``groups`` (mixed-tier mode) is the ``(tier, rows)`` layout of
            the tier-sorted batch, served in ONE step via per-row-group
            plane-prefix GEMMs; ``tier`` (serialized mode) runs the whole
            batch at one tier.  ``perm`` (traced) maps batch rows into the
            sorted group order and changes per chunk without retracing.

            ``sampling`` — the traced ``(keys [B,2] uint32, draws [B]
            i32, temperature [B] f32, top_k [B] i32)`` tuple — moves
            token selection into the scan (``spec.sampling``): rows with
            temperature 0 still take the raw-logits argmax exactly, so a
            greedy batch stays bit-identical to the legacy path.  The
            engine always passes it; ``None`` keeps the historical
            trace/signature for direct lowering callers
            (``decode_dispatch_count`` and HLO-inspection tests) and
            returns the legacy 5-tuple without draw state."""
            if groups is not None:
                rt_eff = self.rt.for_groups(groups, perm)
            else:
                rt_eff = self.rt.for_tier(tier)
            if tp is not None:
                rt_eff = dataclasses.replace(rt_eff, tp=tp)

            if sampling is None:
                def step(carry: Any, _: Any) -> Any:
                    tok, caches, remaining = carry
                    active = remaining > 0
                    logits, caches = self.model.decode_step(
                        params, rt_eff, caches, tokens=tok[:, None],
                        active=active)
                    with jax.named_scope("sample"):
                        nxt = jnp.argmax(logits[:, -1],
                                         axis=-1).astype(jnp.int32)
                    tok = jnp.where(active, nxt, tok)
                    remaining = remaining - active.astype(jnp.int32)
                    return (tok, caches, remaining), (tok, active)

                (tok, caches, remaining), (toks, actives) = jax.lax.scan(
                    step, (tok, caches, remaining), None, length=n_steps)
                return caches, tok, remaining, toks, actives

            keys, draws, temp, topk = sampling

            def sstep(carry: Any, _: Any) -> Any:
                tok, caches, remaining, draws = carry
                active = remaining > 0
                logits, caches = self.model.decode_step(
                    params, rt_eff, caches, tokens=tok[:, None],
                    active=active)
                with jax.named_scope("sample"):
                    nxt, draws = sampling_lib.sample_tokens(
                        logits[:, -1], keys, draws, temp, topk,
                        active=active)
                tok = jnp.where(active, nxt, tok)
                remaining = remaining - active.astype(jnp.int32)
                return (tok, caches, remaining, draws), (tok, active)

            (tok, caches, remaining, draws), (toks, actives) = jax.lax.scan(
                sstep, (tok, caches, remaining, draws), None, length=n_steps)
            return caches, tok, remaining, draws, toks, actives

        def spec_round_fn(params: Any, caches: Any, tok: Any,
                          remaining: Any, perm_draft: Any, perm_verify: Any,
                          spec_mask: Any,
                          sampling: Tuple[Any, Any, Any, Any], k: int,
                          draft_groups: GroupLayout,
                          verify_groups: GroupLayout) -> Any:
            """One speculative round: k chained draft steps at the draft
            layout, then ONE multi-token verify forward at the normal
            layout, acceptance, and cache rollback — all inside one jit.

            Draft phase: spec slots (``spec_mask``) decode at their draft
            tier (``draft_groups`` retags just their rows — a plane
            prefix of the same preloaded store, zero re-preparation)
            WITHOUT consuming budget; plain slots sharing the batch run
            these k steps as ordinary decode steps (their tokens/actives
            come back in ``dtoks``/``dact``).  The spec slots' draft-tier
            cache writes are then discarded (``slots.merge_slots``).

            Verify phase: the (k+1)-token window ``[t0, d1..dk]`` runs
            through ``model.verify_step`` — one batched forward whose
            position-j logits are bit-identical to sequential decode.
            Acceptance is rejection sampling against the verify-tier
            distributions (greedy rows degenerate to exact prefix match),
            ``e = min(m+1, remaining)`` tokens emit, and the KV/SSM lanes
            of rejected positions roll back (length truncation +
            stacked-step re-selection).  ``k`` and the two layouts are
            STATIC; masks, budgets, permutations and sampling state are
            traced."""
            keys, draws, temp, topk = sampling
            rt_draft = self.rt.for_groups(draft_groups, perm_draft)
            rt_verify = self.rt.for_groups(verify_groups, perm_verify)
            orig = caches
            tok0 = tok

            def draft_step(carry: Any, _: Any) -> Any:
                tok, caches, remaining, draws = carry
                active = remaining > 0
                plain_active = active & (~spec_mask)
                logits, caches = self.model.decode_step(
                    params, rt_draft, caches, tokens=tok[:, None],
                    active=active)
                with jax.named_scope("sample"):
                    row = logits[:, -1]
                    qp = sampling_lib.sampling_probs(row, temp, topk)
                    nxt, draws = sampling_lib.sample_tokens(
                        row, keys, draws, temp, topk, active=active)
                tok = jnp.where(active, nxt, tok)
                # Spec slots draft beyond their budget accounting: they
                # spend ``remaining`` only at emission (verify) time.
                remaining = remaining - plain_active.astype(jnp.int32)
                return (tok, caches, remaining, draws), (tok, plain_active,
                                                         qp)

            (tok, caches, remaining, draws), (dtoks, dact, qps) = \
                jax.lax.scan(draft_step, (tok, caches, remaining, draws),
                             None, length=k)

            # Discard the spec slots' draft-tier cache writes; plain slots
            # keep theirs (their draft-phase steps were real decode steps).
            caches = slots_lib.merge_slots(caches, orig, spec_mask)

            drafts = jnp.swapaxes(dtoks, 0, 1)                   # [B, k]
            window = jnp.concatenate([tok0[:, None], drafts], axis=1)
            vlogits, caches = self.model.verify_step(
                params, rt_verify, caches, tokens=window, active=spec_mask)

            batch, width = window.shape                  # width == k + 1
            with jax.named_scope("sample"):            # acceptance
                p = sampling_lib.sampling_probs(
                    vlogits.reshape(batch * width, -1),
                    jnp.repeat(temp, width),
                    jnp.repeat(topk, width)).reshape(batch, width, -1)
                q = jnp.swapaxes(qps, 0, 1)                   # [B, k, V]
                m = spec_lib.accept_counts(drafts, q, p, keys, draws)
                corr = spec_lib.correction_tokens(q, p, m, keys, draws)
                emit = spec_lib.emission_window(drafts, corr, m)
            e = jnp.where(spec_mask, jnp.minimum(m + 1, remaining), 0)

            # Rollback: rewind the KV lengths of rejected window positions
            # and re-select each slot's SSM state at its last emitted
            # position (plain rows: e == 0, mask False, stacked entries
            # all equal their pre-verify state — untouched either way).
            last_idx = jnp.clip(e - 1, 0, width - 1)
            caches = slots_lib.truncate_kv_lengths(
                caches, jnp.int32(width) - e, spec_mask)
            caches = slots_lib.select_verify_step(caches, last_idx)

            last = jnp.take_along_axis(emit, last_idx[:, None],
                                       axis=1)[:, 0]
            tok = jnp.where(spec_mask, last, tok)
            remaining = remaining - e
            spec_sampled = spec_mask & (temp > jnp.float32(0.0))
            draws = draws + jnp.where(
                spec_sampled,
                jnp.int32(spec_lib.accept_draw_events(k)), 0)
            return (caches, tok, remaining, draws, dtoks, dact, emit, e, m)

        # Un-jitted handle kept for trace-only introspection
        # (decode_dispatch_count): jax.make_jaxpr stages the step without
        # running it.  NOTE: it traces the UNSHARDED graph (tp=None) even
        # on a mesh engine — dispatch counts are a per-device property of
        # the kernels, not of the collectives around them.
        self._decode_chunk_fn = decode_chunk_fn
        # Speculative rounds run unsharded only (submit rejects spec on a
        # mesh engine with a clean error).
        self._spec_round = serve_jit(
            spec_round_fn,
            static_argnames=("k", "draft_groups", "verify_groups"))
        if self.mesh is None:
            self._prefill_slot = serve_jit(prefill_slot,
                                           static_argnames=("tier",))
            self._decode_chunk = serve_jit(decode_chunk_fn,
                                           static_argnames=("n_steps", "tier",
                                                            "groups"))
            # Mid-stream KV migration: one jitted requantize serves every
            # (slot, from-tier, to-tier) combination — slot and code are
            # traced.
            self._migrate_kv = serve_jit(slots_lib.migrate_kv_tier)
            # Preemption primitives: cut one slot out of the arena as a
            # batch-1 cache / write a snapshot back into ANY slot — both
            # with the slot index traced (one trace serves every slot).
            self._snapshot_slot = serve_jit(slots_lib.slot_view)
            self._restore_slot = serve_jit(slots_lib.slot_write)
        else:
            (self._prefill_slot, self._decode_chunk, self._migrate_kv,
             self._snapshot_slot, self._restore_slot) = self._mesh_wrap(
                 prefill_slot, decode_chunk_fn)

    # --------------------------------------------------------------- mesh TP
    def _init_mesh_placement(self, mesh: Any) -> tp_serve.TPConfig:
        """Validate the mesh against the model, derive the static TP
        context, and place the prepared store + slot arena.

        Every sharded weight is N-sharded on its last axis; the KV arena
        shards over KV heads when they divide, else (MQA ``num_kv_heads ==
        1``) stays replicated with only query heads sharded.  Divisibility
        is exact-or-error: a non-dividing axis raises here, at
        construction, not mid-stream."""
        if "model" not in mesh.axis_names:
            raise ValueError("serve TP needs a mesh with a 'model' axis, "
                             f"got axes {mesh.axis_names}")
        n = int(mesh.shape["model"])
        cfg = self.model.cfg
        if cfg.num_heads and cfg.num_heads % n != 0:
            raise ValueError(
                f"serve TP: num_heads={cfg.num_heads} does not divide "
                f"across {n} devices")
        kv_shards = bool(cfg.num_kv_heads) and cfg.num_kv_heads % n == 0
        if cfg.num_kv_heads and not kv_shards and cfg.num_kv_heads != 1:
            raise ValueError(
                f"serve TP: num_kv_heads={cfg.num_kv_heads} neither "
                f"divides across {n} devices nor is 1 (the replicated-MQA "
                "fallback)")
        tp = tp_serve.TPConfig(n=n, kv_shards=kv_shards)

        def flat_specs(tree: Any, spec_fn: Any) -> Tuple[Any, Any]:
            flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
            specs = tuple(
                spec_fn(jax.tree_util.keystr(kp), leaf, n=n,
                        kv_shards=kv_shards) for kp, leaf in flat)
            return specs, treedef

        self._p_specs, self._p_def = flat_specs(
            self.params, sharding_rules.serve_tp_param_spec)
        self._c_specs, self._c_def = flat_specs(
            self.arena.caches, sharding_rules.serve_tp_cache_spec)

        def place(tree: Any, specs: Any, treedef: Any) -> Any:
            shardings = jax.tree_util.tree_unflatten(
                treedef, [NamedSharding(mesh, s) for s in specs])
            return jax.device_put(tree, shardings)

        self.params = place(self.params, self._p_specs, self._p_def)
        self.arena.caches = place(self.arena.caches, self._c_specs,
                                  self._c_def)
        return tp

    def _mesh_wrap(self, prefill_slot: Any,
                   decode_chunk_fn: Any) -> Tuple[Any, Any, Any]:
        """Build the jitted shard_map twins of prefill/decode/migrate.

        The wrappers keep the EXACT call signatures ``step()`` /
        ``_admit_free_slots()`` / ``_set_tier()`` use, so the scheduling
        loop is mesh-oblivious: params/caches are flattened to leaf tuples
        (shard_map specs ride the flat tuples — no spec-filled dataclass
        containers), the body re-builds the trees and runs the same inner
        functions with ``tp`` set, and cache shards come back still
        sharded (out_specs = in_specs) so the arena never materializes
        unsharded."""
        mesh, tp = self.mesh, self._tp
        p_specs, p_def = self._p_specs, self._p_def
        c_specs, c_def = self._c_specs, self._c_def
        unflatten = jax.tree_util.tree_unflatten
        rep = P()

        def sharded_prefill(params: Any, caches: Any, slot: Any,
                            tokens: Any, length: Any, kv_code: Any,
                            key: Any, temp: Any, topk: Any,
                            tier: Optional[str] = None) -> Tuple[Any, Any]:
            fp = tuple(jax.tree.leaves(params))
            fc = tuple(jax.tree.leaves(caches))

            def body(fp: Any, fc: Any, slot: Any, tokens: Any, length: Any,
                     kv_code: Any, key: Any, temp: Any,
                     topk: Any) -> Tuple[Any, Any]:
                tok, out_c = prefill_slot(
                    unflatten(p_def, fp), unflatten(c_def, fc), slot,
                    tokens, length, kv_code, key, temp, topk, tier=tier,
                    tp=tp)
                return tok, tuple(jax.tree.leaves(out_c))

            tok, fc2 = jax.shard_map(
                body, mesh=mesh,
                in_specs=(p_specs, c_specs, rep, rep, rep, rep, rep, rep,
                          rep),
                out_specs=(rep, c_specs), check_vma=False)(
                    fp, fc, slot, tokens, length, kv_code, key, temp, topk)
            return tok, unflatten(c_def, fc2)

        def sharded_decode(params: Any, caches: Any, tok: Any,
                           remaining: Any, perm: Any, n_steps: int,
                           tier: Optional[str] = None,
                           groups: Optional[GroupLayout] = None,
                           sampling: Optional[Tuple[Any, Any, Any, Any]]
                           = None) -> Any:
            fp = tuple(jax.tree.leaves(params))
            fc = tuple(jax.tree.leaves(caches))

            if sampling is None:        # legacy trace (lowering callers)
                def body(fp: Any, fc: Any, tok: Any, remaining: Any,
                         perm: Any) -> Any:
                    out_c, tok2, rem2, toks, act = decode_chunk_fn(
                        unflatten(p_def, fp), unflatten(c_def, fc), tok,
                        remaining, perm, n_steps, tier, groups, tp=tp)
                    return (tuple(jax.tree.leaves(out_c)), tok2, rem2,
                            toks, act)

                fc2, tok2, rem2, toks, act = jax.shard_map(
                    body, mesh=mesh,
                    in_specs=(p_specs, c_specs, rep, rep, rep),
                    out_specs=(c_specs, rep, rep, rep, rep),
                    check_vma=False)(fp, fc, tok, remaining, perm)
                return unflatten(c_def, fc2), tok2, rem2, toks, act

            # Sampling state is replicated (a single ``rep`` prefix-spec
            # covers the whole tuple): every device computes the identical
            # threefry draws, so the sampled stream is mesh-width
            # independent by construction.
            def sbody(fp: Any, fc: Any, tok: Any, remaining: Any,
                      perm: Any, sampling: Any) -> Any:
                out_c, tok2, rem2, draws, toks, act = decode_chunk_fn(
                    unflatten(p_def, fp), unflatten(c_def, fc), tok,
                    remaining, perm, n_steps, tier, groups, tp=tp,
                    sampling=sampling)
                return (tuple(jax.tree.leaves(out_c)), tok2, rem2, draws,
                        toks, act)

            fc2, tok2, rem2, draws, toks, act = jax.shard_map(
                sbody, mesh=mesh,
                in_specs=(p_specs, c_specs, rep, rep, rep, rep),
                out_specs=(c_specs, rep, rep, rep, rep, rep),
                check_vma=False)(fp, fc, tok, remaining, perm, sampling)
            return unflatten(c_def, fc2), tok2, rem2, draws, toks, act

        def sharded_migrate(caches: Any, slot: Any, code: Any) -> Any:
            fc = tuple(jax.tree.leaves(caches))

            def body(fc: Any, slot: Any, code: Any) -> Any:
                out = slots_lib.migrate_kv_tier(unflatten(c_def, fc), slot,
                                                code)
                return tuple(jax.tree.leaves(out))

            fc2 = jax.shard_map(body, mesh=mesh,
                                in_specs=(c_specs, rep, rep),
                                out_specs=c_specs, check_vma=False)(
                                    fc, slot, code)
            return unflatten(c_def, fc2)

        # Preemption twins: slot_view/slot_write slice the SLOT axis, which
        # is never sharded, so the cache leaf specs apply to the batch-1
        # sub-tree unchanged — snapshots come back sharded exactly like the
        # arena (device_get then assembles the global snapshot), and a host
        # snapshot restores onto any slot with the arena staying sharded.
        def sharded_snapshot(caches: Any, slot: Any) -> Any:
            fc = tuple(jax.tree.leaves(caches))

            def body(fc: Any, slot: Any) -> Any:
                sub = slots_lib.slot_view(unflatten(c_def, fc), slot)
                return tuple(jax.tree.leaves(sub))

            fs = jax.shard_map(body, mesh=mesh, in_specs=(c_specs, rep),
                               out_specs=c_specs, check_vma=False)(fc, slot)
            return unflatten(c_def, fs)

        def sharded_restore(caches: Any, sub: Any, slot: Any) -> Any:
            fc = tuple(jax.tree.leaves(caches))
            fs = tuple(jax.tree.leaves(sub))

            def body(fc: Any, fs: Any, slot: Any) -> Any:
                out = slots_lib.slot_write(unflatten(c_def, fc),
                                           unflatten(c_def, fs), slot)
                return tuple(jax.tree.leaves(out))

            fc2 = jax.shard_map(body, mesh=mesh,
                                in_specs=(c_specs, c_specs, rep),
                                out_specs=c_specs,
                                check_vma=False)(fc, fs, slot)
            return unflatten(c_def, fc2)

        return (serve_jit(sharded_prefill, static_argnames=("tier",)),
                serve_jit(sharded_decode,
                          static_argnames=("n_steps", "tier", "groups")),
                serve_jit(sharded_migrate),
                serve_jit(sharded_snapshot),
                serve_jit(sharded_restore))

    # ----------------------------------------------------- dispatch counting
    def decode_dispatch_count(self, *, groups: Optional[GroupLayout] = None,
                              tier: Optional[str] = None,
                              n_steps: int = 1) -> int:
        """Pallas dispatches of ONE jitted decode chunk for a given layout.

        Traces the decode step (``jax.make_jaxpr`` — nothing executes, no
        device work) and counts ``pallas_call`` equations, recursing into
        the scan body.  With the fused grouped path this count is CONSTANT
        in the number of tier groups; the per-group path pays one GEMM
        dispatch chain per group.  Keys ``EngineStats.decode_dispatches``
        when ``count_dispatches=True``."""
        perm = jnp.arange(self.max_batch, dtype=jnp.int32)

        def chunk(p: Any, c: Any, t: Any, r: Any, pm: Any) -> Any:
            return self._decode_chunk_fn(p, c, t, r, pm, n_steps, tier,
                                         groups)

        closed = jax.make_jaxpr(chunk)(
            self.params, self.arena.caches, jnp.asarray(self._tok),
            jnp.asarray(self._remaining), perm)
        return ops.count_pallas_calls(closed)

    # ------------------------------------------------------------------ clock
    @property
    def clock(self) -> float:
        """Deterministic scheduler clock: decode steps executed so far.
        Submission times, queue waits and ``Request.deadline`` are priced
        in these ticks."""
        return float(self.stats.decode_steps)

    @property
    def has_work(self) -> bool:
        """True while anything waits or decodes."""
        return self.scheduler.has_work

    def _sync_telemetry(self) -> None:
        """Mirror EngineStats into the telemetry registry (called after
        every state-changing op so the twins are ALWAYS consistent — the
        fuzz harness asserts equality after each operation)."""
        if self.telemetry is not None:
            self.telemetry.sync_stats(
                self.stats, queue_depth=self.scheduler.queue_depth)

    # ----------------------------------------------------------------- intake
    def submit(self, request: Request) -> RequestHandle:
        """Queue one request; returns its streaming :class:`RequestHandle`.

        Host-side: validates against engine limits.  On a tiered engine the
        queued copy always carries a concrete tier name (the schedule's
        default when the caller left it None).

        With an overload-controlling policy (``SLOPolicy(shed=True)``) the
        policy's admission decision runs HERE, before anything is queued: a
        deadline request whose projected completion exceeds modeled
        capacity is refused — its handle comes back already in the terminal
        SHED state (fail fast beats a guaranteed miss) — or, with
        ``auto_tier``, downtiered to the fastest-fitting tier (counted in
        ``EngineStats.tier_autoselects`` like any deadline-driven retag)."""
        with span("serve.submit", uid=request.uid):
            return self._submit(request)

    def _submit(self, request: Request) -> RequestHandle:
        _validate_request(request, self.max_len, self._seen_uids)
        if self.schedule is None:
            if request.tier is not None:
                raise ValueError(
                    f"request {request.uid}: tier {request.tier!r} on an "
                    "engine without a PrecisionSchedule")
            request = dataclasses.replace(request)
        else:
            # Normalize onto a copy: every QUEUED request carries a concrete
            # tier name, but the caller's object stays untouched.
            if request.tier is not None \
                    and request.tier not in self.schedule.tiers:
                raise ValueError(
                    f"request {request.uid}: unknown tier {request.tier!r}; "
                    f"engine serves {sorted(self.schedule.tiers)}")
            request = dataclasses.replace(
                request, tier=request.tier or self.schedule.default_tier)
        if request.sampling is not None:
            request.sampling.validate()
        if request.spec is not None:
            spec = request.spec
            spec.validate()
            if self.schedule is None:
                raise ValueError(
                    f"request {request.uid}: speculative decoding needs an "
                    "engine with a PrecisionSchedule (the draft tier is a "
                    "plane prefix of the superplane store)")
            if spec.draft_tier not in self.schedule.tiers:
                raise ValueError(
                    f"request {request.uid}: unknown draft tier "
                    f"{spec.draft_tier!r}; engine serves "
                    f"{sorted(self.schedule.tiers)}")
            if not self.mixed_tiers:
                raise ValueError(
                    f"request {request.uid}: speculative decoding needs "
                    "mixed_tiers=True (draft rows are retagged in the "
                    "decode group layout)")
            if self.mesh is not None:
                raise ValueError(
                    f"request {request.uid}: speculative decoding is not "
                    "supported on a mesh engine; submit without spec or "
                    "use an unsharded engine")
        self._seen_uids.add(request.uid)
        handle = RequestHandle(request, self, submitted_at=self.clock)
        self.handles[request.uid] = handle
        pol = self.scheduler.policy
        if isinstance(pol, SLOPolicy) and pol.shed:
            decision = pol.admission_decision(
                request, list(self.scheduler.waiting), self._running_info(),
                self.max_batch, self.scheduler.submitted_at, self.clock)
            if decision == "shed":
                handle._mark_shed(self.clock)
                self.stats.sheds += 1
                if self.telemetry is not None:
                    self.telemetry.on_submit(handle, ticks=self.clock)
                    self.telemetry.on_shed(handle, ticks=self.clock)
                self._sync_telemetry()
                return handle
            if decision != "admit":
                request.tier = decision        # our normalized copy
                self.stats.tier_autoselects += 1
        # Handle and scheduler share the SAME (normalized) Request object,
        # so a QUEUED set_tier re-tags the queue entry in place.
        self.scheduler.submit(request, now=self.clock)
        if self.telemetry is not None:
            self.telemetry.on_submit(handle, ticks=self.clock)
        self._sync_telemetry()
        return handle

    # -------------------------------------------------------------- migration
    def _set_tier(self, handle: RequestHandle, tier: str) -> None:
        """Move one request to another tier (``RequestHandle.set_tier``).

        QUEUED: re-tag the waiting request (it re-prices for SLO admission
        and prefills at the new tier).  RUNNING (mixed-tier mode only): if
        the tiers map to different KV precisions, requantize the slot's
        live KV lane in place (jitted; bit-identical to quantizing the
        slot's dequantized cache directly at the target precision), then
        re-tag the slot — the weight plane prefix switches at the next
        group-layout derivation.  FINISHED: error."""
        if self.schedule is None:
            raise ValueError("set_tier needs an engine with a "
                             "PrecisionSchedule")
        if tier not in self.schedule.tiers:
            raise ValueError(f"unknown tier {tier!r}; engine serves "
                             f"{sorted(self.schedule.tiers)}")
        if handle.done:
            raise RuntimeError(
                f"request {handle.uid} already {handle.status.value}; "
                "cannot migrate its tier")
        old = handle.request.tier
        if tier == old:
            return
        if handle.status is RequestStatus.SUSPENDED:
            raise RuntimeError(
                f"request {handle.uid} is suspended; its KV snapshot is "
                "pinned at its tier — let it resume (or cancel it) first")
        if handle.status is RequestStatus.QUEUED:
            handle.request.tier = tier      # shared with the queue entry
            return
        # RUNNING: live-slot migration.
        if not self.mixed_tiers:
            raise RuntimeError(
                "mid-stream tier migration needs mixed_tiers=True (a "
                "serialized decode batch runs one tier at a time)")
        slot = handle.slot
        assert slot is not None
        kv_migrated = False
        if self._mixed_kv:
            new_code = self.schedule.kv_code_for(tier)
            if new_code != self.schedule.kv_code_for(old):
                self.arena.caches = self._migrate_kv(
                    self.arena.caches, jnp.int32(slot), jnp.int32(new_code))
                self.stats.kv_migrations += 1
                kv_migrated = True
        handle.request.tier = tier          # shared with the SlotState
        self.arena.tiers[slot] = tier
        self.stats.tier_migrations += 1
        if self.telemetry is not None:
            self.telemetry.on_migrate(
                uid=handle.uid, old_tier=old, new_tier=tier, kv=kv_migrated,
                ticks=self.clock)
        self._sync_telemetry()

    # ------------------------------------------------------------- preemption
    @property
    def suspended(self) -> Dict[int, SuspendedState]:
        """Read-only view of the live suspensions (uid -> snapshot)."""
        return dict(self._suspended)

    def _running_info(self) -> List[RunningEntry]:
        """The RUNNING slots as the overload-control hooks price them:
        ``(slot, request, decode tokens still owed, submission tick)``."""
        return [(slot, s.request, int(s.remaining),
                 self.handles[s.uid].submitted_at)
                for slot, s in self.scheduler.occupied()]

    def preempt(self, uid: int) -> SuspendedState:
        """Suspend a RUNNING request, freeing its slot.

        The slot's KV lane slice is cut out of the arena as a batch-1
        cache (``slot_view`` — every leaf, so the recurrent/SSM state
        rows, the KV tier code and the cache length ride along), pulled to
        host memory, and bundled with the host decode state (emitted
        tokens, owed budget, last emitted token — the next decode input)
        into a slot-agnostic :class:`SuspendedState`.  With ``spill_dir``
        the snapshot is persisted through the checkpoint subsystem
        (async, atomic step dirs) and dropped from host memory.

        The request re-enters the waiting queue at its ORIGINAL submission
        tick — a preemption never extends its deadline budget — and its
        handle flips to SUSPENDED.  Re-admission is prefill-free
        (``slot_write`` into whichever slot frees up) and the resumed
        stream is token-identical to the uninterrupted run.

        Preemption is only legal BETWEEN scheduling rounds: calling this
        from inside ``step()`` (e.g. an ``on_token`` callback) raises —
        mid-round the device cache has already advanced past the host
        token bookkeeping, so a snapshot there would tear the state."""
        with span("serve.preempt", uid=uid):
            return self._preempt(uid)

    def _preempt(self, uid: int) -> SuspendedState:
        if self._in_round:
            raise RuntimeError(
                "preempt() called from inside a scheduling round (e.g. an "
                "on_token callback); preemption is only legal between "
                "engine.step() calls")
        handle = self.handles.get(uid)
        if handle is None:
            raise KeyError(f"unknown uid {uid}")
        if handle.status is not RequestStatus.RUNNING:
            raise RuntimeError(
                f"request {uid} is {handle.status.value}; only RUNNING "
                "requests can be preempted")
        slot = handle.slot
        assert slot is not None
        state = self.scheduler.evict(slot)
        sub = self._snapshot_slot(self.arena.caches, jnp.int32(slot))
        host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), sub)
        nbytes = int(sum(leaf.nbytes for leaf in jax.tree.leaves(host)))
        sus = SuspendedState(
            request=state.request, tokens=list(state.tokens),
            remaining=int(state.remaining),
            last_token=int(self._tok[slot]), cache=host, nbytes=nbytes,
            draws=int(self._draws[slot]))
        if self._spill_dir is not None:
            sus = self._spill(sus)
        self._suspended[uid] = sus
        self.arena.tiers[slot] = None
        handle._mark_suspended()
        pol = self.scheduler.policy
        if isinstance(pol, SLOPolicy):
            # Re-pricing: a half-served stream owes only its remainder.
            pol.remaining_tokens[uid] = sus.remaining
        self.scheduler.submit(state.request, now=handle.submitted_at)
        self.stats.preemptions += 1
        if self.telemetry is not None:
            self.telemetry.on_suspend(handle, ticks=self.clock)
        self._sync_telemetry()
        return sus

    def _policy_preempt(self) -> None:
        """Run the policy's displacement rule between rounds
        (``SLOPolicy(preempt=True)``): while a deadlined waiting request
        is out of slack, free slots cannot absorb the queue, and a
        strictly-slacker RUNNING victim exists, suspend the victim.  The
        strict-inequality rule in :meth:`SLOPolicy.preempt_victim`
        guarantees termination (each displaced request re-enters the queue
        with MORE slack than the one it yielded to)."""
        pol = self.scheduler.policy
        if not isinstance(pol, SLOPolicy) or not pol.preempt:
            return
        for _ in range(self.max_batch):      # safety bound, never binding
            waiting = list(self.scheduler.waiting)
            urgent = [r for r in waiting if r.deadline is not None
                      and pol.weighted_slack(r, self.scheduler.submitted_at,
                                             self.clock) <= pol.preempt_slack]
            if len(self.scheduler.free_slots()) >= len(urgent):
                return             # this round's admission absorbs the urgent
            victim = pol.preempt_victim(
                waiting, self._running_info(),
                self.scheduler.submitted_at, self.clock)
            if victim is None:
                return
            self.preempt(victim)

    def _resume_into(self, slot: int, req: Request,
                     sus: SuspendedState) -> None:
        """Prefill-free re-admission: write the snapshot's batch-1 cache
        into the (freshly admitted, possibly different) slot and restore
        the host decode state exactly where preemption cut it."""
        cache = sus.cache if sus.cache is not None else self._unspill(sus)
        self.arena.caches = self._restore_slot(
            self.arena.caches, cache, jnp.int32(slot))
        self.arena.tiers[slot] = req.tier
        state = self.scheduler.slots[slot]
        assert state is not None
        state.tokens = list(sus.tokens)
        state.remaining = sus.remaining
        # The cache holds the prompt and every emitted token but the last.
        self._kv_len[slot] = len(req.prompt) + len(sus.tokens) - 1
        self._tok[slot] = sus.last_token
        self._remaining[slot] = sus.remaining
        self._load_sampling_state(slot, req, draws=sus.draws)
        self._slice_start[slot] = self.clock
        pol = self.scheduler.policy
        if isinstance(pol, SLOPolicy):
            pol.remaining_tokens.pop(req.uid, None)
        self.handles[req.uid]._mark_admitted(slot, self.clock)
        self.stats.resumes += 1
        if self.telemetry is not None:
            self.telemetry.on_admit(self.handles[req.uid], slot=slot,
                                    ticks=self.clock, resumed=True)

    def _load_sampling_state(self, slot: int, req: Request, *,
                             draws: int) -> None:
        """Load one slot's host-mirrored sampling state from its request
        (admission and resume share this): the raw request key, the draw
        counter (0 at fresh admission, the snapshot's at resume — the
        stream continues exactly where it stopped), temperature and
        top-k.  Greedy requests (no sampling / temperature 0) keep the
        all-zero state and never consume randomness."""
        sp = req.sampling
        seed = sp.seed if sp is not None else 0
        self._key[slot] = sampling_lib.request_key(seed)
        self._temp[slot] = np.float32(sp.temperature if sp is not None
                                      else 0.0)
        self._topk[slot] = sp.top_k if sp is not None else 0
        self._draws[slot] = draws

    def _slot_template(self) -> Any:
        """Shape/dtype skeleton of one slot's cache slice (restore target
        for spilled snapshots) — evaluated abstractly, cached."""
        if self._slot_template_cache is None:
            self._slot_template_cache = jax.eval_shape(
                lambda c: slots_lib.slot_view(c, jnp.int32(0)),
                self.arena.caches)
        return self._slot_template_cache

    def _spill(self, sus: SuspendedState) -> SuspendedState:
        """Persist a snapshot through the checkpoint subsystem and drop it
        from host memory.  ``keep=0`` disables the checkpointer's GC —
        live spills must never be collected out from under their
        suspended requests; :meth:`_unspill` removes each step dir as its
        request resumes."""
        assert self._spill_dir is not None
        if self._spiller is None:
            self._spiller = checkpoint_lib.AsyncCheckpointer(
                self._spill_dir, keep=0)
        step = self._spill_counter
        self._spill_counter += 1
        self._spiller.save(step, sus.cache, extra={
            "uid": sus.request.uid, "tokens": sus.tokens,
            "remaining": sus.remaining, "last_token": sus.last_token,
            "tier": sus.request.tier})
        self.stats.spill_bytes += sus.nbytes
        return dataclasses.replace(sus, cache=None, spill_step=step)

    def _unspill(self, sus: SuspendedState) -> Any:
        """Read a spilled snapshot back (waiting out the async writer) and
        delete its step dir — resumed spills do not accumulate on disk."""
        assert self._spiller is not None and sus.spill_step is not None \
            and self._spill_dir is not None
        self._spiller.wait()
        tree, _ = checkpoint_lib.restore(self._spill_dir, sus.spill_step,
                                         target=self._slot_template())
        checkpoint_lib.remove(self._spill_dir, sus.spill_step)
        return tree

    def cancel(self, uid: int) -> None:
        """Abort a QUEUED or SUSPENDED request: drop its queue entry (and
        its submission-clock entry — cancellation must not leak scheduler
        state), discard any snapshot/spill, and flip its handle to the
        terminal SHED state with whatever tokens it had streamed.

        RUNNING requests cannot be cancelled directly — preempt first (the
        slot state must be detached from the device before it can be
        discarded); already-terminal requests raise."""
        handle = self.handles.get(uid)
        if handle is None:
            raise KeyError(f"unknown uid {uid}")
        if handle.done:
            raise RuntimeError(
                f"request {uid} already {handle.status.value}")
        if handle.status is RequestStatus.RUNNING:
            raise RuntimeError(
                f"request {uid} is running; preempt it first (cancel only "
                "drops queued/suspended state)")
        self.scheduler.cancel(uid)
        sus = self._suspended.pop(uid, None)
        if sus is not None and sus.spill_step is not None:
            assert self._spiller is not None and self._spill_dir is not None
            self._spiller.wait()
            checkpoint_lib.remove(self._spill_dir, sus.spill_step)
        pol = self.scheduler.policy
        if isinstance(pol, SLOPolicy):
            pol.remaining_tokens.pop(uid, None)
        handle._mark_shed(self.clock)
        self.stats.sheds += 1
        if self.telemetry is not None:
            self.telemetry.on_shed(handle, ticks=self.clock)
        self._sync_telemetry()

    # ------------------------------------------------------------- scheduling
    def _bucket_pad(self,
                    prompt: npt.NDArray[np.int32]) -> Tuple[Any, int]:
        """Right-pad to the next bucket multiple (few jit retraces)."""
        plen = len(prompt)
        bucket = -(-plen // self.prompt_bucket) * self.prompt_bucket
        bucket = min(bucket, self.max_len)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :plen] = prompt
        return padded, plen

    def _emit_token(self, state: Any, token: int, tier: Optional[str],
                    speculative: bool = False) -> TokenEvent:
        """Record one emitted token on slot state + handle; returns the
        event.  ``final`` fires on the request's last owed token and flips
        its handle to FINISHED.

        ``tier`` is the tier the token was DECODED at (snapshotted at
        dispatch): a ``set_tier`` issued from an on_token callback
        mid-round must not relabel the round's remaining, already-computed
        tokens.  ``speculative`` marks tokens emitted by a speculative
        round (accepted drafts + corrections — all verified at ``tier``).
        A callback that raises is deferred to the end of the round
        (``_raise_deferred``) so slot bookkeeping stays in sync with the
        device state."""
        index = len(state.tokens)
        state.emit(token)
        sp = state.request.sampling
        event = TokenEvent(uid=state.uid, token=token, index=index,
                           tier=tier, final=state.done,
                           sampled=sp is not None and sp.temperature > 0.0,
                           speculative=speculative)
        self.handles[state.uid]._push(event, self.clock,
                                      defer=self._defer_error)
        if self.telemetry is not None:
            self.telemetry.on_token(event, ticks=self.clock)
        return event

    def _admit_free_slots(self) -> List[TokenEvent]:
        """Fill free slots from the waiting queue and prefill each admitted
        request individually (mixed-tier mode: the policy's pick into ANY
        slot; serialized mode: only requests matching the active tier).
        Returns the prefill-emitted first tokens as events.

        A SUSPENDED request that wins a slot resumes instead of
        prefilling: its snapshot is written back into the slot and its
        decode state picks up exactly where preemption cut it (no event —
        its already-emitted tokens were streamed before suspension)."""
        events: List[TokenEvent] = []
        for slot in self.scheduler.free_slots():
            if self.schedule is None or self.mixed_tiers:
                req = self.scheduler.admit(slot, now=self.clock)
            else:
                if self._active_tier is None:
                    # Idle decode batch: the policy's next pick chooses the
                    # next tier (FIFO across tier groups by default).
                    pick = self.scheduler.peek(now=self.clock)
                    if pick is None:
                        break
                    nxt = pick.tier
                    if self.stats.decode_chunks:
                        self.stats.tier_switches += nxt != self._last_tier
                    self._active_tier = nxt
                req = self.scheduler.admit(slot, tier=self._active_tier,
                                           now=self.clock)
            if req is None:
                break
            sus = self._suspended.pop(req.uid, None)
            if sus is not None:
                self._resume_into(slot, req, sus)
                continue
            self._auto_select_tier(req)
            padded, plen = self._bucket_pad(np.asarray(req.prompt))
            with span("serve.admit", uid=req.uid, tier=req.tier,
                      prompt_len=plen, bucket=padded.shape[1]):
                events.append(self._admit(slot, req, padded, plen))
        return events

    def _admit(self, slot: int, req: Request, padded: Any,
               plen: int) -> TokenEvent:
        """Prefill one admitted request into ``slot`` and emit its first
        token."""
        kv_code = self.schedule.kv_code_for(req.tier) \
            if self._mixed_kv else 0
        self._load_sampling_state(slot, req, draws=0)
        with span("serve.prefill", tier=req.tier, prompt_len=plen,
                  bucket=padded.shape[1]):
            tok, self.arena.caches = self._prefill_slot(
                self.params, self.arena.caches, jnp.int32(slot),
                jnp.asarray(padded), jnp.int32(plen), jnp.int32(kv_code),
                jnp.asarray(self._key[slot]),
                jnp.float32(self._temp[slot]),
                jnp.int32(self._topk[slot]), tier=req.tier)
        self.arena.tiers[slot] = req.tier
        self._kv_len[slot] = plen
        self.stats.prefills += 1
        self.stats.prefill_tokens += plen
        # The first token was draw event 0 (sampled rows only).
        if self._temp[slot] > 0.0:
            self._draws[slot] = 1
        self._slice_start[slot] = self.clock
        with span("serve.wait_first_token"):
            first = int(tok)
        state = self.scheduler.slots[slot]
        assert state is not None
        self.handles[req.uid]._mark_admitted(slot, self.clock)
        if self.telemetry is not None:
            self.telemetry.on_admit(self.handles[req.uid], slot=slot,
                                    ticks=self.clock)
        with span("serve.emit"):
            event = self._emit_token(state, first,
                                     req.tier)  # token 1 of max_new
        self._tok[slot] = first
        self._remaining[slot] = state.remaining
        return event

    def _auto_select_tier(self, req: Request) -> None:
        """Deadline-aware tier auto-selection at admission
        (``SLOPolicy(auto_tier=True)``): retag the just-admitted request —
        the same request-object retag a QUEUED ``set_tier`` performs, and
        still before its slot prefills, so the new tier drives the prefill
        dispatch, the slot's weight plane prefix AND its KV lane precision.
        Mixed-tier admission only: a serialized batch is pinned to its
        active tier.  Best-effort requests (no deadline) keep their
        requested tier."""
        pol = self.scheduler.policy
        if (self.schedule is None or not self.mixed_tiers
                or not isinstance(pol, SLOPolicy) or not pol.auto_tier):
            return
        tier = pol.select_tier(req, self.handles[req.uid].submitted_at,
                               self.clock)
        if tier is not None and tier != req.tier \
                and tier in self.schedule.tiers:
            req.tier = tier          # shared with handle and queue copy
            self.stats.tier_autoselects += 1

    def _release_done(self) -> None:
        """Release exhausted slots and clear their arena tier tags."""
        for slot in self.scheduler.release_done():
            self.arena.tiers[slot] = None

    def _group_layout(self, tiers: Optional[Sequence[Optional[str]]] = None
                      ) -> Tuple[GroupLayout, npt.NDArray[np.int32]]:
        """Derive the per-step mixed-tier layout from the slot tier tags.

        Returns ``(groups, perm)``: ``groups`` is the jit-STATIC tuple of
        ``(tier, rows)`` in schedule tier order (free slots ride along in
        the default tier's group — their lanes are masked anyway), ``perm``
        the TRACED int32 [B] slot order realizing it.  The jit key space is
        the set of tier multisets over ``max_batch`` slots, not the set of
        slot assignments.

        ``tiers`` overrides the arena's tier vector (same length) — the
        speculative draft phase derives its layout from a copy with the
        spec slots retagged to their draft tiers.

        Derivations are memoized on the slot-tier vector
        (``EngineStats.layout_cache_hits`` / ``layout_cache_misses``): the
        steady state of a serving loop repeats a handful of layouts, so the
        per-step host work collapses to one dict lookup."""
        schedule = self.schedule
        assert schedule is not None
        if tiers is None:
            tiers = self.arena.tiers
        cache_key = tuple(tiers)
        cached = self._layout_cache.get(cache_key)
        if cached is not None:
            self.stats.layout_cache_hits += 1
            return cached
        self.stats.layout_cache_misses += 1
        rank = {t: i for i, t in enumerate(schedule.tier_names)}
        default = schedule.default_tier
        slot_tiers = [t if t is not None else default for t in tiers]
        order = sorted(range(self.max_batch),
                       key=lambda s: (rank[slot_tiers[s]], s))
        groups: List[List[Any]] = []
        for s in order:
            t = slot_tiers[s]
            if groups and groups[-1][0] == t:
                groups[-1][1] += 1
            else:
                groups.append([t, 1])
        layout = (tuple((t, n) for t, n in groups),
                  np.asarray(order, np.int32))
        self._layout_cache[cache_key] = layout
        return layout

    # ------------------------------------------------------------------- run
    def step(self) -> List[TokenEvent]:
        """One scheduling round: admit into free slots, then run one jitted
        decode chunk (serving the occupied slots' tiers together in mixed
        mode, or the single active tier in serialized mode) and account its
        tokens.  Returns every token emitted this round (prefill first
        tokens + decode tokens, in emission order); an idle engine returns
        ``[]`` without dispatching anything.

        With ``SLOPolicy(preempt=True)`` the policy's displacement rule
        runs FIRST (between rounds — the only point a snapshot is
        coherent), so displaced slots free before admission fills the
        round's batch; ``_in_round`` then pins preemption out for the rest
        of the round (an ``on_token`` callback calling ``preempt`` would
        tear host state from the already-advanced device cache)."""
        with span("serve.step"):
            if self.schedule is not None and not self.mixed_tiers:
                if not self.scheduler.occupied():
                    if self._active_tier is not None:  # keep across idles
                        self._last_tier = self._active_tier
                    self._active_tier = None       # batch drained: re-tier
            self._time_slice_preempt()
            self._policy_preempt()
            self._in_round = True
            try:
                return self._step_round()
            finally:
                self._in_round = False
                self._sync_telemetry()

    def _time_slice_preempt(self) -> None:
        """Time-slice fairness (``SLOPolicy(time_slice=N)``): between
        rounds, voluntarily preempt best-effort (deadline-free) RUNNING
        slots whose current slice has run at least N scheduler ticks while
        other requests wait.  Victims re-enter the queue aged as if
        submitted NOW (scheduler-side only — the handle keeps its true
        ``submitted_at``, so ``queue_wait`` semantics are untouched), so
        the waiting requests they yielded to win the FIFO/age tie-break
        and a two-request ping-pong cannot livelock the batch.  At most
        ``len(waiting)`` victims per round: slices never free more slots
        than there is demand for."""
        pol = self.scheduler.policy
        if not isinstance(pol, SLOPolicy) or pol.time_slice is None:
            return
        n_waiting = len(self.scheduler.waiting)
        if n_waiting == 0:
            return
        expired = [(self._slice_start.get(slot, self.clock), state.uid)
                   for slot, state in self.scheduler.occupied()
                   if state.request.deadline is None
                   and self.clock - self._slice_start.get(slot, self.clock)
                   >= pol.time_slice]
        expired.sort()                     # oldest slice first
        for _, uid in expired[:n_waiting]:
            self.preempt(uid)
            self.scheduler.submitted_at[uid] = self.clock
            self.stats.time_slice_preemptions += 1

    def _sampling_args(self) -> Tuple[Any, Any, Any, Any]:
        """The traced sampling-state tuple every decode dispatch takes."""
        return (jnp.asarray(self._key), jnp.asarray(self._draws),
                jnp.asarray(self._temp), jnp.asarray(self._topk))

    def _step_round(self) -> List[TokenEvent]:
        """The round body (see :meth:`step`): admit, decode, account."""
        events = self._admit_free_slots()
        self._release_done()                       # max_new_tokens == 1 cases
        occupied = self.scheduler.occupied()
        if not occupied:
            self._raise_deferred()
            return events
        if any(s.request.spec is not None for _, s in occupied):
            return self._spec_dispatch(occupied, events)
        # Trim the chunk so a tail of all-finished steps is never dispatched
        # (keyed per distinct length: at most decode_chunk jit entries).
        n_steps = int(min(self.decode_chunk,
                          max(s.remaining for _, s in occupied)))
        groups: Optional[GroupLayout]
        with span("serve.decode", n_steps=n_steps) as sp:
            if self.schedule is not None and self.mixed_tiers:
                groups, perm = self._group_layout()
                tier = None
                sp.set_metadata(layout=format_group_layout(groups))
                if self.count_dispatches \
                        and groups not in self.stats.decode_dispatches:
                    self.stats.decode_dispatches[groups] = \
                        self.decode_dispatch_count(groups=groups)
            else:
                groups, perm = None, np.zeros((self.max_batch,), np.int32)
                tier = self._active_tier
                sp.set_metadata(tier=tier)
            (self.arena.caches, tok, remaining, draws, toks, actives) = \
                self._decode_chunk(self.params, self.arena.caches,
                                   jnp.asarray(self._tok),
                                   jnp.asarray(self._remaining),
                                   jnp.asarray(perm), n_steps=n_steps,
                                   tier=tier, groups=groups,
                                   sampling=self._sampling_args())
        with span("serve.wait_decode"):
            # copies: host arrays stay writable
            self._tok = np.array(tok)
            self._remaining = np.array(remaining)
            self._draws = np.array(draws)
            toks = np.asarray(toks)               # [n_steps, B]
            actives = np.asarray(actives)
        self.stats.decode_chunks += 1
        self.stats.decode_steps += n_steps
        self.stats.decode_slot_steps += int(actives.sum())
        self.stats.decode_idle_slot_steps += int((~actives).sum())
        self._account_kv_reads(actives)
        if self.schedule is not None:
            occupied_tiers = {self.arena.tiers[slot]
                              for slot, _ in occupied} if self.mixed_tiers \
                else {tier}
            self.stats.mixed_tier_chunks += len(occupied_tiers) > 1
            for t in occupied_tiers:
                assert t is not None    # tiered engines tag occupied slots
                by_tier = self.stats.decode_steps_by_tier
                by_tier[t] = by_tier.get(t, 0) + n_steps
            tk = self.stats.tokens_by_tier
            for slot, _ in occupied:
                t = self.arena.tiers[slot] if self.mixed_tiers else tier
                assert t is not None
                tk[t] = tk.get(t, 0) + int(actives[:, slot].sum())
        # Emission in true stream order (step-major): per-request order is
        # identical to the historical slot-major loop.  Event tiers are the
        # tiers the chunk DISPATCHED at (a set_tier from a callback must
        # not relabel tokens already computed at the old width).
        if self.schedule is None:
            etier: Dict[int, Optional[str]] = {s_: None for s_, _ in occupied}
        elif self.mixed_tiers:
            etier = {s_: self.arena.tiers[s_] for s_, _ in occupied}
        else:
            etier = {s_: tier for s_, _ in occupied}
        with span("serve.emit"):
            for s in range(n_steps):
                for slot, state in occupied:
                    if actives[s, slot]:
                        events.append(self._emit_token(
                            state, int(toks[s, slot]), etier[slot]))
            self._release_done()
        self._raise_deferred()
        return events

    def _local_heads(self) -> Tuple[int, int]:
        """Query and KV heads of attention on one device: divided over the
        mesh's ``model`` axis as ``layers.attention_apply`` divides them
        (KV heads only when they shard)."""
        cfg, tp = self.model.cfg, self._tp
        h, kvh = cfg.num_heads, cfg.num_kv_heads
        if tp is None:
            return h, kvh
        return h // tp.n, kvh // tp.n if tp.kv_shards else kvh

    def _account_kv_reads(self, actives: npt.NDArray[np.bool_]) -> None:
        """KV read accounting of one plain decode chunk (``actives``
        [n_steps, B]): each step appends one position to every active slot,
        then decode attention reads every slot up to its fill point."""
        lens = np.minimum(self._kv_len[None, :] + np.cumsum(actives, axis=0),
                          self.max_len)
        self._kv_len = lens[-1]
        if not self._has_kv:
            return
        reserved = lens.size * self.max_len
        self.stats.kv_positions_reserved += reserved
        self.stats.kv_positions_read += int(
            kv_attention.positions_fetched(lens).sum()) \
            if self._kv_kernel else reserved

    def _spec_dispatch(self, occupied: List[Tuple[int, Any]],
                       events: List[TokenEvent]) -> List[TokenEvent]:
        """One speculative scheduling round (any occupied slot with
        ``Request.spec`` routes the whole round here).

        Host side of ``spec_round_fn``: derive the draft layout (spec
        slots retagged to their draft tiers — zero weight re-preparation,
        the draft model is a plane prefix of the store), run the jitted
        round (k draft steps + ONE verify window forward + rollback), then
        emit — plain slots' draft-phase tokens step-major first (for them
        those were ordinary decode steps), then each spec slot's accepted
        window.  The scheduler clock advances k+1 ticks (k draft + 1
        verify).  Slots with different ``k`` share the round at the
        largest ``k`` (drafting deeper is harmless; acceptance is exact
        either way)."""
        spec_states = [(slot, s) for slot, s in occupied
                       if s.request.spec is not None]
        k = max(s.request.spec.k for _, s in spec_states)
        width = k + 1
        spec_mask = np.zeros((self.max_batch,), bool)
        draft_tiers = list(self.arena.tiers)
        for slot, s in spec_states:
            spec_mask[slot] = True
            draft_tiers[slot] = s.request.spec.draft_tier
        n_spec = len(spec_states)
        with span("serve.spec_round", k=k, n_spec=n_spec) as sp:
            draft_groups, perm_d = self._group_layout(tiers=draft_tiers)
            verify_groups, perm_v = self._group_layout()
            sp.set_metadata(draft_layout=format_group_layout(draft_groups),
                            layout=format_group_layout(verify_groups))
            (self.arena.caches, tok, remaining, draws, dtoks, dact, win, e,
             m) = self._spec_round(
                self.params, self.arena.caches, jnp.asarray(self._tok),
                jnp.asarray(self._remaining), jnp.asarray(perm_d),
                jnp.asarray(perm_v), jnp.asarray(spec_mask),
                self._sampling_args(), k=k, draft_groups=draft_groups,
                verify_groups=verify_groups)
        with span("serve.wait_spec"):
            self._tok = np.array(tok)
            self._remaining = np.array(remaining)
            self._draws = np.array(draws)
            dtoks = np.asarray(dtoks)                   # [k, B]
            dact = np.asarray(dact)                     # [k, B]
            win = np.asarray(win)                       # [B, k+1]
            e = np.asarray(e)
            m = np.asarray(m)
        self.stats.decode_chunks += 1
        self.stats.decode_steps += width
        self.stats.spec_rounds += 1
        self.stats.spec_draft_steps += k
        self.stats.spec_verify_steps += 1
        self.stats.spec_drafted += k * n_spec
        self.stats.spec_accepted += int(
            np.minimum(m[spec_mask], e[spec_mask]).sum())
        self.stats.spec_emitted += int(e[spec_mask].sum())
        # Fill points after the round: plain slots appended their active
        # draft steps, spec slots keep their emitted window (the rollback
        # rewound the rest).
        self._kv_len += dact.sum(axis=0) + np.where(spec_mask, e, 0)
        # Slot-step accounting identity (decode_slot_steps +
        # decode_idle_slot_steps == decode_steps * max_batch): spec slots
        # are busy all k+1 steps, plain slots their active draft steps.
        busy = int(dact.sum()) + width * n_spec
        self.stats.decode_slot_steps += busy
        self.stats.decode_idle_slot_steps += width * self.max_batch - busy
        by_tier = self.stats.decode_steps_by_tier
        draft_occ = {draft_tiers[slot] for slot, _ in occupied}
        verify_occ = {self.arena.tiers[slot] for slot, _ in occupied}
        for t in draft_occ:
            assert t is not None
            by_tier[t] = by_tier.get(t, 0) + k
        for t in verify_occ:
            assert t is not None
            by_tier[t] = by_tier.get(t, 0) + 1
        self.stats.mixed_tier_chunks += len(draft_occ | verify_occ) > 1
        tk = self.stats.tokens_by_tier
        for slot, _ in occupied:
            t = self.arena.tiers[slot]
            assert t is not None
            n = int(dact[:, slot].sum())
            if spec_mask[slot]:
                n += int(e[slot])
            if n:
                tk[t] = tk.get(t, 0) + n
        # Emission: plain slots step-major through the draft phase, then
        # each spec slot's verified window (decoded AT the verify tier).
        etier = {slot: self.arena.tiers[slot] for slot, _ in occupied}
        with span("serve.emit"):
            for s_i in range(k):
                for slot, state in occupied:
                    if dact[s_i, slot]:
                        events.append(self._emit_token(
                            state, int(dtoks[s_i, slot]), etier[slot]))
            for slot, state in spec_states:
                for j in range(int(e[slot])):
                    events.append(self._emit_token(
                        state, int(win[slot, j]), etier[slot],
                        speculative=True))
            self._release_done()
        self._raise_deferred()
        return events

    def drain(self) -> Dict[int, List[int]]:
        """Step until idle; returns {uid: tokens} for every finished
        request (the streaming loop's terminal collect)."""
        while self.has_work:
            self.step()
        return dict(self.scheduler.finished)

    def run(self, requests: Sequence[Request]) -> Dict[int, List[int]]:
        """Blocking compatibility wrapper over the incremental core:
        submit every request, drain, collect — token-identical to the
        historical batch API.  A request shed at admission maps to its
        (empty) partial stream rather than raising."""
        for r in requests:
            self.submit(r)
        finished = self.drain()
        return {r.uid: finished.get(r.uid, list(self.handles[r.uid].tokens))
                for r in requests}

    def retire(self, uid: int) -> List[int]:
        """Drop a terminal (FINISHED or SHED) request's host state — its
        handle (buffered events + tokens), its results entry, and its uid
        reservation — and return the tokens (a SHED request's partial
        stream).

        This is the long-running server's bound on per-request host
        memory: handles and finished-token lists otherwise live for the
        engine's lifetime.  A retired uid may be submitted again."""
        handle = self.handles.get(uid)
        if handle is None:
            raise KeyError(f"unknown uid {uid}")
        if not handle.done:
            raise RuntimeError(f"request {uid} is {handle.status.value}; "
                               "only FINISHED/SHED requests can be retired")
        tokens = self.scheduler.finished.pop(uid, None)
        if tokens is None:
            tokens = list(handle.tokens)     # SHED: whatever was streamed
        # SHED requests may still own suspended-state residue (a request
        # cancelled while SUSPENDED frees it in cancel(); this is the
        # belt-and-braces path so retiring EVERY terminal request provably
        # leaves the engine empty — the fuzz harness asserts exactly that).
        sus = self._suspended.pop(uid, None)
        if sus is not None and sus.spill_step is not None:
            assert self._spiller is not None and self._spill_dir is not None
            self._spiller.wait()
            checkpoint_lib.remove(self._spill_dir, sus.spill_step)
        pol = self.scheduler.policy
        if isinstance(pol, SLOPolicy):
            pol.remaining_tokens.pop(uid, None)
        del self.handles[uid]
        self._seen_uids.discard(uid)
        return tokens

    @property
    def results(self) -> Dict[int, List[int]]:
        return dict(self.scheduler.finished)


@dataclasses.dataclass
class _BatchState:
    """Host state of the batch the reference engine currently decodes."""

    batch: List[Request]
    caches: Any
    tok: Any                      # [B] int32 device array
    outs: List[List[int]]
    step_idx: int
    max_new: int


class BatchServeEngine(_DeferredErrors):
    """Reference batch-at-a-time baseline (the seed's scheduling): admit up
    to ``max_batch`` requests, prefill them together, decode EVERY slot for
    the batch-wide ``max_new_tokens``, then refill the whole batch.

    Implements the same incremental ``submit`` / ``step`` / ``drain``
    surface as :class:`ServeEngine` (one ``step`` = one batch-wide decode
    step, starting a new batch when idle), with ``run`` as the blocking
    wrapper — so the :class:`Engine` protocol covers both.  Kept for parity
    tests and benchmarks: its outputs are exact per request (right-padded
    prefill with per-row true lengths), but finished slots keep burning
    decode steps until the batch max — the waste the continuous-batching
    engine eliminates.

    On a tiered runtime the baseline runs EVERY request at ONE fixed tier
    (``tier`` pins it; the schedule's default otherwise) — it has no
    per-request switching, and ``RequestHandle.set_tier`` on its handles
    always raises.  Its KV cache follows that tier's ``kv_tiers``
    precision when the schedule declares one (and ``kv_bits`` was left
    None), which makes it the fixed-precision reference for the mixed
    per-slot KV arena."""

    def __init__(self, model: LM, params: Any, rt: Runtime, *,
                 max_batch: int = 8, max_len: int = 512,
                 kv_bits: Optional[int] = None, packed: bool = False,
                 tier: Optional[str] = None,
                 telemetry: Optional[Any] = None) -> None:
        self.model = model
        if rt.schedule is not None and tier is not None \
                and tier not in rt.schedule.tiers:
            raise ValueError(f"unknown tier {tier!r}; engine serves "
                             f"{sorted(rt.schedule.tiers)}")
        self.tier_name: Optional[str] = None
        if rt.schedule is not None:
            if kv_bits is None:
                kv_bits = rt.schedule.kv_bits_for(tier)
            self.tier_name = tier if tier is not None \
                else rt.schedule.default_tier
            rt = rt.for_tier(tier)
        self.rt = rt
        self.params, _ = _ensure_prepared(params, rt, model, packed)
        self.max_batch = max_batch
        self.max_len = max_len
        self.kv_bits = kv_bits
        self.stats = EngineStats()
        # Minimal telemetry (lifecycle + stat twins): the baseline exists
        # for parity runs, and ``--baseline --metrics`` should still export.
        self.telemetry = telemetry
        if telemetry is not None:
            telemetry.attach_engine(num_slots=max_batch)
        self.handles: Dict[int, RequestHandle] = {}
        self.results: Dict[int, List[int]] = {}
        self._queue: List[Request] = []
        self._seen_uids: Set[int] = set()
        self._active: Optional[_BatchState] = None
        self._prefill = serve_jit(
            lambda p, c, t, ln: model.prefill(p, rt, c, tokens=t,
                                              seq_lengths=ln))
        self._decode = serve_jit(
            lambda p, c, t: model.decode_step(p, rt, c, tokens=t))

    # ------------------------------------------------------------------ clock
    @property
    def clock(self) -> float:
        """Scheduler clock: decode steps executed (same units as
        ServeEngine's)."""
        return float(self.stats.decode_steps)

    @property
    def has_work(self) -> bool:
        return bool(self._queue) or self._active is not None

    # ----------------------------------------------------------------- intake
    def submit(self, request: Request) -> RequestHandle:
        """Queue one request (same admission contract as ServeEngine —
        :func:`_validate_request`); returns its handle.  Batches form in
        submission order, ``max_batch`` at a time, whenever ``step`` finds
        no active batch."""
        _validate_request(request, self.max_len, self._seen_uids)
        if request.spec is not None:
            raise ValueError(
                f"request {request.uid}: speculative decoding needs "
                "ServeEngine (the reference baseline has no draft/verify "
                "dispatch)")
        if request.sampling is not None:
            request.sampling.validate()
            if request.sampling.temperature > 0.0:
                raise ValueError(
                    f"request {request.uid}: temperature sampling needs "
                    "ServeEngine (the reference baseline decodes greedily); "
                    "temperature=0.0 SamplingParams are accepted as greedy")
        self._seen_uids.add(request.uid)
        handle = RequestHandle(request, self, submitted_at=self.clock)
        self.handles[request.uid] = handle
        self._queue.append(request)
        if self.telemetry is not None:
            self.telemetry.on_submit(handle, ticks=self.clock)
            self.telemetry.sync_stats(self.stats,
                                      queue_depth=len(self._queue))
        return handle

    def _set_tier(self, handle: RequestHandle, tier: str) -> None:
        raise RuntimeError(
            "BatchServeEngine pins one tier for every request; per-request "
            "tier migration needs ServeEngine (mixed_tiers=True)")

    def cancel(self, uid: int) -> None:
        """Abort a QUEUED request (the reference baseline has no
        preemption, so only not-yet-batched requests can be cancelled);
        flips its handle to the terminal SHED state."""
        handle = self.handles.get(uid)
        if handle is None:
            raise KeyError(f"unknown uid {uid}")
        if handle.done:
            raise RuntimeError(
                f"request {uid} already {handle.status.value}")
        if handle.status is not RequestStatus.QUEUED:
            raise RuntimeError(
                f"request {uid} is {handle.status.value}; BatchServeEngine "
                "can only cancel QUEUED requests (no preemption)")
        self._queue = [r for r in self._queue if r.uid != uid]
        handle._mark_shed(self.clock)
        self.stats.sheds += 1
        if self.telemetry is not None:
            self.telemetry.on_shed(handle, ticks=self.clock)
            self.telemetry.sync_stats(self.stats,
                                      queue_depth=len(self._queue))

    # ------------------------------------------------------------------- run
    def _start_batch(self) -> None:
        """Form + prefill the next batch (up to ``max_batch`` requests in
        submission order)."""
        batch = self._queue[: self.max_batch]
        self._queue = self._queue[self.max_batch:]
        b = len(batch)
        plen = max(len(r.prompt) for r in batch)
        prompts = np.zeros((b, plen), np.int32)
        lengths = np.zeros((b,), np.int32)
        for i, r in enumerate(batch):
            prompts[i, :len(r.prompt)] = r.prompt    # right-pad
            lengths[i] = len(r.prompt)
        caches = self.model.init_cache(b, self.max_len, kv_bits=self.kv_bits)
        logits, caches = self._prefill(self.params, caches,
                                       jnp.asarray(prompts),
                                       jnp.asarray(lengths))
        self.stats.prefills += b
        self.stats.prefill_tokens += int(lengths.sum())
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        for i, r in enumerate(batch):
            self.handles[r.uid]._mark_admitted(i, self.clock)
            if self.telemetry is not None:
                self.telemetry.on_admit(self.handles[r.uid], slot=i,
                                        ticks=self.clock)
        self._active = _BatchState(
            batch=batch, caches=caches, tok=tok,
            outs=[[] for _ in range(b)], step_idx=0,
            max_new=max(r.max_new_tokens for r in batch))

    def step(self) -> List[TokenEvent]:
        """One batch-wide decode step (starting a new batch when idle):
        emit the current token for every request still owed one, then
        advance the whole batch — finished slots keep burning decode work
        until the batch max (the baseline's defining waste).  Returns the
        emitted tokens; ``[]`` when fully idle."""
        if self._active is None:
            if not self._queue:
                return []
            self._start_batch()
        a = self._active
        assert a is not None
        events: List[TokenEvent] = []
        for i, r in enumerate(a.batch):
            if a.step_idx < r.max_new_tokens:
                token = int(a.tok[i])
                a.outs[i].append(token)
                event = TokenEvent(uid=r.uid, token=token, index=a.step_idx,
                                   tier=self.tier_name,
                                   final=a.step_idx == r.max_new_tokens - 1)
                events.append(event)
                self.handles[r.uid]._push(event, self.clock,
                                          defer=self._defer_error)
                if self.telemetry is not None:
                    self.telemetry.on_token(event, ticks=self.clock)
        logits, a.caches = self._decode(self.params, a.caches, a.tok[:, None])
        a.tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        self.stats.decode_steps += 1
        self.stats.decode_slot_steps += len(a.batch)
        a.step_idx += 1
        if self.telemetry is not None:
            self.telemetry.sync_stats(self.stats,
                                      queue_depth=len(self._queue))
        if a.step_idx >= a.max_new:
            for i, r in enumerate(a.batch):
                self.results[r.uid] = a.outs[i][: r.max_new_tokens]
            self._active = None
        self._raise_deferred()
        return events

    def drain(self) -> Dict[int, List[int]]:
        """Step until idle; returns {uid: tokens} for finished requests."""
        while self.has_work:
            self.step()
        return dict(self.results)

    def run(self, requests: Sequence[Request]) -> Dict[int, List[int]]:
        """Serve the list batch-at-a-time (blocking wrapper over
        submit/step/drain); returns {uid: tokens}.

        Validation is all-or-nothing (the historical contract): a bad
        request anywhere in the list raises before ANY of them is queued
        or its uid burned."""
        seen = set(self._seen_uids)
        for r in requests:
            _validate_request(r, self.max_len, seen)
            seen.add(r.uid)
        for r in requests:
            self.submit(r)
        finished = self.drain()
        return {r.uid: finished[r.uid] for r in requests}

    def retire(self, uid: int) -> List[int]:
        """Drop a terminal request's host state and release its uid (same
        contract as :meth:`ServeEngine.retire`)."""
        handle = self.handles.get(uid)
        if handle is None:
            raise KeyError(f"unknown uid {uid}")
        if not handle.done:
            raise RuntimeError(f"request {uid} is {handle.status.value}; "
                               "only FINISHED/SHED requests can be retired")
        tokens = self.results.pop(uid, None)
        if tokens is None:
            tokens = list(handle.tokens)     # SHED before batching: empty
        del self.handles[uid]
        self._seen_uids.discard(uid)
        return tokens
