"""Per-layer mixed-precision policy — the configuration surface of the paper.

The accelerator's value proposition is *fully mixed-precision* inference:
every layer may run at any (w_bits, a_bits) in 2..8.  This module holds the
policy objects the model layers consult, plus a sensitivity-based allocator
(HAWQ-style gradient-squared proxy) that picks per-layer bitwidths under an
average-bit budget.
"""
from __future__ import annotations

import dataclasses
import fnmatch
import re
from typing import Dict, Optional

# Matmul execution backends, lowest to highest fidelity to the accelerator:
#   dense       - bf16 matmul, no quantization (fp baseline)
#   fake_quant  - QAT: quantize-dequantize with STE, dense matmul (training)
#   decomposed  - integer plane-decomposed matmul, pure-JAX HLO (serving/dry-run)
#   pallas      - the Pallas TPU kernel (serving hot path; interpret on CPU)
BACKENDS = ("dense", "fake_quant", "decomposed", "pallas")


@dataclasses.dataclass(frozen=True)
class LayerPrecision:
    """One layer's (w_bits, a_bits, signedness, backend) operating point.

    Frozen and hashable on purpose: LayerPrecision values travel as
    JIT-STATIC data — they key traces (e.g. as members of the per-row-group
    tuples in ``kernels.ops.matmul``) and must never be traced arrays."""

    w_bits: int = 8
    a_bits: int = 8
    w_signed: bool = True
    a_signed: bool = True
    backend: str = "fake_quant"

    def __post_init__(self):
        if not (2 <= self.w_bits <= 8 and 2 <= self.a_bits <= 8):
            raise ValueError(f"bits out of 2..8: w={self.w_bits} a={self.a_bits}")
        if self.backend not in BACKENDS:
            raise ValueError(f"backend {self.backend!r} not in {BACKENDS}")

    def with_backend(self, backend: str) -> "LayerPrecision":
        """This precision with the execution backend swapped."""
        return dataclasses.replace(self, backend=backend)


DEFAULT_PRECISION = LayerPrecision()


@dataclasses.dataclass
class PrecisionPolicy:
    """Maps layer names (glob patterns) to LayerPrecision.

    First matching rule wins; ``default`` applies otherwise.  Layer names are
    hierarchical, e.g. ``layers.3.attn.q_proj`` or ``layers.*.mlp.up_proj``.
    """

    rules: Dict[str, LayerPrecision] = dataclasses.field(default_factory=dict)
    default: LayerPrecision = DEFAULT_PRECISION

    def lookup(self, name: str) -> LayerPrecision:
        """Precision for one layer name (first matching rule, else default).

        Pure host-side string matching — call it OUTSIDE traced code or on
        static names only (layer names are static throughout the model)."""
        for pattern, prec in self.rules.items():
            if fnmatch.fnmatch(name, pattern):
                return prec
        return self.default

    def with_backend(self, backend: str) -> "PrecisionPolicy":
        """Every rule and the default re-targeted to ``backend``."""
        return PrecisionPolicy(
            rules={k: v.with_backend(backend) for k, v in self.rules.items()},
            default=self.default.with_backend(backend),
        )

    def average_bits(self, layer_names, param_counts=None) -> float:
        """Parameter-weighted mean weight bitwidth over ``layer_names``."""
        names = list(layer_names)
        counts = param_counts or [1] * len(names)
        tot = sum(counts)
        return sum(self.lookup(n).w_bits * c for n, c in zip(names, counts)) / tot


def uniform_policy(w_bits: int, a_bits: int, backend: str = "fake_quant",
                   a_signed: bool = True) -> PrecisionPolicy:
    """Single-precision policy: every layer at (w_bits, a_bits)."""
    return PrecisionPolicy(default=LayerPrecision(
        w_bits=w_bits, a_bits=a_bits, backend=backend, a_signed=a_signed))


# --------------------------------------------------------- runtime schedules
# Runtime-reconfigurable serving: ONE superplane weight store (prepared at 8
# bits), many named quality tiers selectable per request at decode time.
# A PrecisionSchedule replaces the per-prepare PrecisionPolicy for tiered
# engines: it maps (layer name x tier name) -> effective LayerPrecision, and
# every tier's w_bits must be reachable by plane-prefix truncation
# (decompose.RUNTIME_W_BITS) so switching tiers never re-prepares a weight.

from repro.core.decompose import RUNTIME_W_BITS  # noqa: E402


# Per-request KV-cache precision tiers (the decode-memory analogue of the
# weight plane prefix): a schedule may map each tier to a KV storage
# precision — None (bf16), 8 (int8) or 4 (int4-packed).  16 is the internal
# tier code for bf16 in the per-slot arena.
KV_TIER_CHOICES = (None, 8, 4)


@dataclasses.dataclass
class PrecisionSchedule:
    """Named runtime tiers over one preloaded superplane weight store.

    ``tiers`` maps tier name -> that tier's default LayerPrecision; ``rules``
    optionally refines single tiers per layer-name glob (first match wins,
    same contract as PrecisionPolicy).  All precisions must share
    ``w_signed`` (signedness is baked into the stored MSB plane) and use an
    integer serving backend with an even, truncatable ``w_bits``.

    ``kv_tiers`` optionally maps tier name -> KV-cache storage precision
    (None = bf16, 8 = int8, 4 = int4-packed; tiers left out default to
    bf16).  When set, a tiered engine allocates ONE mixed per-slot KV arena
    and every admitted request's slot stores K/V at its tier's KV
    precision — a low tier then shrinks both its weight-plane reads and its
    decode-memory footprint.  Tier names and the derived mode set are
    jit-static; the per-slot tier assignment is traced data
    (``KVCache.kv_bits``)."""

    tiers: Dict[str, LayerPrecision]
    rules: Dict[str, Dict[str, LayerPrecision]] = dataclasses.field(
        default_factory=dict)
    default_tier: Optional[str] = None
    kv_tiers: Optional[Dict[str, Optional[int]]] = None

    def __post_init__(self):
        if not self.tiers:
            raise ValueError("a PrecisionSchedule needs at least one tier")
        if self.default_tier is None:
            self.default_tier = next(iter(self.tiers))
        if self.default_tier not in self.tiers:
            raise ValueError(f"default tier {self.default_tier!r} not in "
                             f"{sorted(self.tiers)}")
        for t in self.rules:
            if t not in self.tiers:
                raise ValueError(f"rules for unknown tier {t!r}")
        if self.kv_tiers is not None:
            for t, kb in self.kv_tiers.items():
                if t not in self.tiers:
                    raise ValueError(f"kv_tiers for unknown tier {t!r}")
                if kb not in KV_TIER_CHOICES:
                    raise ValueError(
                        f"kv tier must be one of {KV_TIER_CHOICES} "
                        f"(None = bf16), got {kb!r} for tier {t!r}")
        signs = set()
        for prec in self._all_precisions():
            if prec.backend not in ("decomposed", "pallas"):
                raise ValueError(
                    f"tier backend must be an integer serving backend, got "
                    f"{prec.backend!r}")
            if prec.w_bits not in RUNTIME_W_BITS:
                raise ValueError(
                    f"tier w_bits must be plane-truncatable {RUNTIME_W_BITS},"
                    f" got {prec.w_bits}")
            signs.add(prec.w_signed)
        if len(signs) > 1:
            raise ValueError("all tiers must share w_signed: the sign mode "
                             "is baked into the preloaded MSB plane")

    def _all_precisions(self):
        for prec in self.tiers.values():
            yield prec
        for by_layer in self.rules.values():
            yield from by_layer.values()

    @property
    def tier_names(self):
        return tuple(self.tiers)

    @property
    def w_signed(self) -> bool:
        return next(iter(self.tiers.values())).w_signed

    # ------------------------------------------------------------ kv tiers
    def kv_bits_for(self, tier: Optional[str] = None) -> Optional[int]:
        """KV storage precision of a tier (None = bf16) — what a
        fixed-precision reference engine at that tier uses globally."""
        tier = self.default_tier if tier is None else tier
        if tier not in self.tiers:
            raise KeyError(f"unknown tier {tier!r}; have {sorted(self.tiers)}")
        if self.kv_tiers is None:
            return None
        return self.kv_tiers.get(tier)

    def kv_code_for(self, tier: Optional[str] = None) -> int:
        """Per-slot arena tier code of a tier (16 = bf16, 8, 4)."""
        kb = self.kv_bits_for(tier)
        return 16 if kb is None else kb

    @property
    def kv_modes(self) -> Optional[tuple]:
        """Static mode set the mixed per-slot KV arena must serve
        (descending tier codes), or None when no kv_tiers are declared."""
        if self.kv_tiers is None:
            return None
        codes = {self.kv_code_for(t) for t in self.tiers}
        return tuple(sorted(codes, reverse=True))

    def tier_bits(self, tier: Optional[str] = None) -> tuple:
        """A tier's default ``(w_bits, a_bits)`` operating point — what the
        hwmodel prices admission with (``energy.relative_tier_costs``).
        Per-layer rule refinements are deliberately ignored here: admission
        is priced per request, not per layer."""
        tier = self.default_tier if tier is None else tier
        if tier not in self.tiers:
            raise KeyError(f"unknown tier {tier!r}; have {sorted(self.tiers)}")
        prec = self.tiers[tier]
        return (prec.w_bits, prec.a_bits)

    def lookup(self, name: str, tier: Optional[str] = None) -> LayerPrecision:
        tier = self.default_tier if tier is None else tier
        if tier not in self.tiers:
            raise KeyError(f"unknown tier {tier!r}; have {sorted(self.tiers)}")
        for pattern, prec in self.rules.get(tier, {}).items():
            if fnmatch.fnmatch(name, pattern):
                return prec
        return self.tiers[tier]

    def policy_for(self, tier: Optional[str] = None) -> PrecisionPolicy:
        """Materialize one tier as a plain PrecisionPolicy — what a
        fixed-precision engine prepared natively at that tier uses (the
        bit-exact reference for the runtime-truncated path)."""
        tier = self.default_tier if tier is None else tier
        if tier not in self.tiers:
            raise KeyError(f"unknown tier {tier!r}; have {sorted(self.tiers)}")
        return PrecisionPolicy(rules=dict(self.rules.get(tier, {})),
                               default=self.tiers[tier])

    def prepare_policy(self) -> PrecisionPolicy:
        """The max-precision policy the superplane store is prepared under
        (8-bit; per-layer signedness from the schedule)."""
        default = next(iter(self.tiers.values()))
        return PrecisionPolicy(default=dataclasses.replace(
            default, w_bits=8, a_bits=8))

    # -------------------------------------------------------- persistence
    def to_json_dict(self) -> Dict:
        """JSON-able dict form (exact round-trip via :meth:`from_json_dict`;
        the format lives in :mod:`repro.autoprec.schedule_io`, which also
        reads/writes whole files)."""
        from repro.autoprec import schedule_io
        return schedule_io.schedule_to_dict(self)

    @classmethod
    def from_json_dict(cls, d: Dict) -> "PrecisionSchedule":
        """Rebuild (and re-validate) a schedule from its dict form."""
        from repro.autoprec import schedule_io
        return schedule_io.schedule_from_dict(d)


def uniform_schedule(tiers: Dict[str, tuple],
                     backend: str = "decomposed",
                     a_signed: bool = True,
                     kv_tiers: Optional[Dict[str, Optional[int]]] = None
                     ) -> PrecisionSchedule:
    """Schedule from ``{name: (w_bits, a_bits)}`` pairs, uniform per tier.

    ``kv_tiers`` optionally maps tier names to KV-cache storage precisions
    (None = bf16, 8, 4) — see :class:`PrecisionSchedule`."""
    return PrecisionSchedule(tiers={
        name: LayerPrecision(w_bits=w, a_bits=a, backend=backend,
                             a_signed=a_signed)
        for name, (w, a) in tiers.items()}, kv_tiers=kv_tiers)


def format_group_layout(layout) -> str:
    """Stable label text of a mixed-tier group layout, the ``(tier, rows)``
    runs of a tier-sorted decode batch:
    ``(("8/8", 2), ("4/4", 1))`` -> ``"8/8x2+4/4x1"``."""
    return "+".join(f"{tier}x{rows}" for tier, rows in layout)


def allocate_bits_by_sensitivity(sensitivities: Dict[str, float],
                                 param_counts: Dict[str, int],
                                 avg_bits: float,
                                 choices=(2, 4, 6, 8),
                                 a_bits: int = 8,
                                 backend: str = "fake_quant") -> PrecisionPolicy:
    """Greedy sensitivity-based bit allocation (HAWQ-flavoured).

    Start everything at min(choices); repeatedly grant one step of extra
    precision to the layer with the best marginal sensitivity reduction per
    budget unit until the parameter-weighted average bitwidth budget is
    exhausted.  A scalar sensitivity models a symmetric quantizer whose
    error halves per extra bit (``sens * 2^-bits``).

    Thin wrapper over :func:`repro.autoprec.search.greedy_trajectory` (the
    measured-sensitivity search core) so the two allocators cannot drift.
    ``choices`` defaults to the EVEN widths the runtime superplane path can
    actually serve (``PrecisionSchedule`` validates against
    ``decompose.RUNTIME_W_BITS``); odd widths may still be requested
    explicitly for the QAT/fake-quant policy path, which has no
    plane-prefix constraint.
    """
    from repro.autoprec.search import greedy_trajectory

    names = sorted(sensitivities)
    missing = [n for n in names if n not in param_counts]
    if missing:
        raise ValueError(f"param_counts misses layers {missing}")
    # Synthetic (layer, width) divergence table from the scalar prior; the
    # budget is the classic parameter-weighted total-bits cap.
    sens = {n: {b: sensitivities[n] * 2.0 ** (-b) for b in choices}
            for n in names}
    layer_cost = {n: {b: float(b * param_counts[n]) for b in choices}
                  for n in names}
    budget = avg_bits * sum(param_counts[n] for n in names)
    traj = greedy_trajectory(names, sens, layer_cost, choices, budget=budget)
    bits = traj[-1]
    rules = {n: LayerPrecision(w_bits=bits[n], a_bits=a_bits, backend=backend)
             for n in names}
    return PrecisionPolicy(rules=rules,
                           default=LayerPrecision(a_bits=a_bits, backend=backend))
