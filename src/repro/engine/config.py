"""Accuracy-configuration subsystem: tiers, error budgets, and the (n, t)
controller.

The paper's headline property is that the splitting point ``t`` is a
*quality knob*: the segmented carry chain shortens the adder critical
path to ``max(t, n - t)`` full-adder delays (paper Fig. 3) at the price
of a deferred-carry error whose magnitude grows with ``t`` (the deferred
carry re-lands one position high with weight 2^t — Eq. 11's MAE
``2^{n+t-1} - 2^{t+1}`` is *increasing* in t, and so is the closed-form
NMED estimate).  Note the direction: unlike truncation-style approximate
multipliers where a wider exact LSP means *less* error, here a larger
``t`` means *more* error and (up to t = n/2) *less* delay — the
accuracy/latency trade-off the controller below navigates.

This module turns that knob into a first-class runtime decision instead
of the historical hardcoded ``n=8, t=4``:

* :func:`resolve_t` — the controller.  It queries
  ``core.error_model.estimate`` (the closed-form Eqs. 9-11 estimator)
  for every candidate split and returns the **cheapest** valid ``t``:
  minimal cycle delay (the same gate-delay model
  ``benchmarks/latency_model`` plots) among the splits whose error
  bounds meet the :class:`ErrorBudget`, ties broken toward the smaller
  (more accurate) split.  Because the error metrics are monotone in
  ``t`` the valid set is the lower interval ``[1, t_max]``, so for any
  budget binding at or below the delay-optimal split the controller
  returns the *unique* cheapest valid ``t = t_max``.
* :class:`QualityTier` / :func:`resolve_tier` — named tiers (``exact``,
  ``high``, ``balanced``, ``draft``) carrying per-GEMM-class
  (mlp / attn / moe) error budgets; resolution produces one
  :class:`~repro.configs.base.LayerQuality` per class.
* :func:`apply_quality` — deploys a resolved tier onto a
  ``ModelConfig`` (per-target overrides ride in
  ``ApproxConfig.overrides``; ``dense``/``moe`` resolve them per call
  site via ``ApproxConfig.for_target``).
* :func:`default_t` — the engine-wide default split for a bit-width,
  resolved from the ``balanced`` tier's mlp budget.  ``default_t(8) ==
  4``: the old hardcoded default is now a *derived* quantity.

The serving layer consumes the same tiers per request
(``repro.serve``: requests carry a tier name, the scheduler resolves it
to the pool's engine config at admission), and the
``accuracy_pareto`` benchmark suite sweeps the controller's candidate
set and records the measured error-vs-throughput Pareto front.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Union

from repro.configs.base import ApproxConfig, LayerQuality, ModelConfig
from repro.core import error_model

__all__ = [
    "T_FA",
    "T_MUX",
    "ripple_delay",
    "segmented_delay",
    "cycle_delay",
    "ErrorBudget",
    "TPoint",
    "QualityError",
    "sweep_t",
    "resolve_t",
    "DEFAULT_N",
    "default_t",
    "KernelTiles",
    "kernel_tiles",
    "QualityTier",
    "QualityConfig",
    "register_tier",
    "get_tier",
    "list_tiers",
    "resolve_tier",
    "apply_quality",
    "tier_cycle_factor",
    "accept_rate_estimate",
    "expected_round_tokens",
    "speculation_gain",
    "best_spec_k",
]


# ------------------------------------------------------------- cycle cost
# Normalized gate-delay model of the per-cycle critical path (paper
# Fig. 3); ``benchmarks/latency_model.py`` imports these so the plotted
# trade-off and the controller's objective cannot drift apart.
T_FA = 1.0  # full-adder delay
T_MUX = 0.4  # fix-to-1 mux + D-FF setup margin


def ripple_delay(n: int) -> float:
    """Accurate multiplier: the carry ripples across all n positions."""
    return n * T_FA


def segmented_delay(n: int, t: int) -> float:
    """Approximate multiplier: the D-FF cuts the chain at ``t``; the
    critical path is the longer segment plus the fix-to-1 mux."""
    return max(t, n - t) * T_FA + T_MUX


def cycle_delay(n: int, t: int) -> float:
    """The controller's cost: per-cycle critical path of the (n, t) design."""
    return segmented_delay(n, t)


# ---------------------------------------------------------- error budgets
@dataclasses.dataclass(frozen=True)
class ErrorBudget:
    """Upper bounds a resolved split must satisfy (``None`` = unbounded).

    ``max_er`` bounds the estimator's ``er_msp`` (itself an upper
    estimate of the true error rate — see the calibration tests), so a
    budget met in closed form is met by the hardware.  ``max_nmed``
    bounds the deferred-carry-ledger MED estimate normalized by the
    maximum product ``(2^n - 1)^2`` (strictly increasing in t — the
    quality knob's native scale).  ``max_mae`` bounds Eq. 11.
    """

    max_er: Optional[float] = None
    max_nmed: Optional[float] = None
    max_mae: Optional[int] = None

    def admits(self, point: "TPoint") -> bool:
        if self.max_er is not None and point.er_bound > self.max_er:
            return False
        if self.max_nmed is not None and point.nmed_est > self.max_nmed:
            return False
        if self.max_mae is not None and point.mae > self.max_mae:
            return False
        return True


@dataclasses.dataclass(frozen=True)
class TPoint:
    """One candidate split with its closed-form metrics and cycle cost."""

    n: int
    t: int
    order: int
    er_bound: float  # estimate(...).er_msp — ER upper estimate (Eq. 10)
    med_abs_est: float  # deferred-carry weight-ledger MED estimate
    nmed_est: float  # med_abs_est / (2^n - 1)^2
    mae: int  # Eq. 11 closed form
    delay: float  # cycle_delay(n, t)


class QualityError(ValueError):
    """No splitting point satisfies the requested error budget."""


def _sweep(n: int, order: int, pa, pb) -> tuple:
    points = []
    max_p = max((2**n - 1) ** 2, 1)
    for t in range(1, max(1, n - 1) + 1):
        est = error_model.estimate(n, t, order=order, pa=pa, pb=pb)
        points.append(TPoint(
            n=n,
            t=t,
            order=order,
            er_bound=est.er_msp,
            med_abs_est=est.med_abs_est,
            nmed_est=est.med_abs_est / max_p,
            mae=error_model.mae_closed_form(n, t),
            delay=cycle_delay(n, t),
        ))
    return tuple(points)


@functools.lru_cache(maxsize=256)
def sweep_t(n: int, *, order: int = 1) -> tuple:
    """Closed-form metrics for every valid split of bit-width ``n``.

    Uniform input marginals (the estimator's default); a measured input
    PDF can be folded in by calling :func:`resolve_t` with explicit
    ``pa``/``pb`` instead.
    """
    return _sweep(n, order, None, None)


def resolve_t(
    n: int,
    budget: ErrorBudget,
    *,
    order: int = 1,
    pa=None,
    pb=None,
    mode: Optional[str] = None,
) -> TPoint:
    """The controller: cheapest split meeting ``budget``.

    Enumerates every candidate ``t``, keeps those whose closed-form
    bounds satisfy the budget, and returns the one minimizing
    ``(cycle_delay, t)`` — the cheapest configuration, ties broken
    toward the more accurate (smaller) split.  Since the error metrics
    grow with ``t``, the valid set is ``[1, t_max]``; whenever the
    budget binds at or below the delay-optimal split the result is the
    unique cheapest valid ``t = t_max``.  Raises :class:`QualityError`
    when even ``t = 1`` exceeds the budget.

    With ``mode`` set, candidates are additionally filtered through the
    static kernel audit (:func:`repro.analysis.audit.certified`): the
    controller can only return a (n, t) whose traced kernel the
    analyzer has proven overflow/gather/VMEM-safe, so an uncertified
    configuration is unreachable through tier resolution by
    construction.  Raises :class:`QualityError` naming certification
    when the audit filter empties the budget-valid set.
    """
    if pa is None and pb is None:
        points = sweep_t(n, order=order)
    else:  # measured input marginals: uncached per-call sweep
        points = _sweep(n, order, pa, pb)
    valid = [p for p in points if budget.admits(p)]
    if not valid:
        raise QualityError(
            f"no splitting point t in [1, {max(1, n - 1)}] for n={n} meets "
            f"{budget} (tightest candidate: t=1 with er<={points[0].er_bound:.3f}, "
            f"nmed<={points[0].nmed_est:.2e}, mae={points[0].mae})"
        )
    if mode is not None:
        from repro.analysis import audit  # lazy: analysis imports us

        certified = [p for p in valid if audit.certified(mode, n, p.t)]
        if not certified:
            raise QualityError(
                f"every budget-valid splitting point for mode {mode!r} at "
                f"n={n} (t in {[p.t for p in valid]}) failed static kernel "
                f"certification; run `python -m repro.launch.analyze` for "
                f"the findings"
            )
        valid = certified
    return min(valid, key=lambda p: (p.delay, p.t))


DEFAULT_N = 8  # LUT-backed modes require n <= 8; the engine-wide default


# ------------------------------------------------- fused-kernel parameters
@dataclasses.dataclass(frozen=True)
class KernelTiles:
    """Blocked-kernel tile sizes for one fused GEMM call.

    ``bm``/``bn``/``bk`` are the (M, N, K) block extents of the
    (M/BM, N/BN, K/BK) reduction grid every fused Pallas GEMM in
    ``repro.kernels`` uses.  Resolved per call by :func:`kernel_tiles`
    from the mode and the controller-chosen (n, t) — this is how a
    :class:`~repro.configs.base.LayerQuality` selection turns into
    concrete fused-kernel launch parameters instead of an outer loop
    around generic kernels.
    """

    bm: int
    bn: int
    bk: int


# Tile sizing.  The chip's compiler takes a block whose last two
# extents are multiples of the (8, 128) vreg tile (sublanes, lanes), so
# every K extent below is a lane multiple and every M extent a sublane
# multiple; all but lowrank clamp ``bm`` to the row count rounded up to 8
# (``repro.kernels.row_block``), so a decode step's few rows are not
# padded to a prefill-sized block.
# Each selection must also pass repro.analysis.vmem.validate_tiles
# (positive, power-of-two, closed-form footprint under budget;
# `launch/analyze.py --report` emits the traced numbers that
# docs/kernels.md is generated from):
#  * seqmul keeps ~8 live uint32 (BM, BK, BN) cubes -> (8, 128, 128),
#    ~4 MiB live, for every n <= 12.
#  * lut selects table rows and columns by one-hot MXU dots, one K index
#    at a time: the (W, 2W) bf16 byte planes plus a few (BM, W) and
#    (W, BN) planes per step, ~4 MiB at (128, 256, 128).
#  * lowrank/packed are pure MXU dot kernels -> 128 tiles.
_SEQMUL_TILES = KernelTiles(bm=8, bn=128, bk=128)
_LUT_TILES = KernelTiles(bm=128, bn=256, bk=128)
_MXU_TILES = KernelTiles(bm=128, bn=128, bk=128)


@functools.lru_cache(maxsize=1024)
def kernel_tiles(mode: str, n: int, t: int) -> KernelTiles:
    """Fused-kernel tile selection for a (mode, n, t) GEMM call.

    The splitting point ``t`` does not change the VMEM footprint (both
    split words live regardless of where the cut sits), so tiles depend
    on the mode's live-set shape and the bit-width; ``t`` itself enters
    the kernel *body* (the in-tile recurrence / the LUT contents).

    Every selection is validated eagerly against the static VMEM model
    (:func:`repro.analysis.vmem.validate_tiles`): a non-positive or
    non-power-of-two extent, or a footprint over the 16 MiB budget,
    raises :class:`~repro.analysis.vmem.TileBudgetError` naming the
    (mode, n, t) — at resolution time, not inside Pallas lowering.
    """
    if mode == "seqmul":
        tiles = _SEQMUL_TILES
    elif mode == "bitexact":
        tiles = _LUT_TILES
    else:
        tiles = _MXU_TILES
    from repro.analysis.vmem import validate_tiles  # lazy: analysis imports us

    validate_tiles(mode, n, t, (tiles.bm, tiles.bn, tiles.bk))
    return tiles


@functools.lru_cache(maxsize=64)
def default_t(n: int = DEFAULT_N) -> int:
    """Engine-wide default split for bit-width ``n``: the ``balanced``
    tier's mlp budget resolved by the controller.  ``default_t(8) == 4``
    — the historical hardcoded default, now derived."""
    tier = get_tier("balanced")
    return resolve_t(n, dict(tier.budgets)["mlp"]).t


# ----------------------------------------------------------------- tiers
@dataclasses.dataclass(frozen=True)
class QualityTier:
    """A named quality level: an engine mode plus per-GEMM-class budgets.

    ``budgets`` maps targets (``mlp`` / ``attn`` / ``moe``) to
    :class:`ErrorBudget`; a target without a budget stays exact.  The
    ``exact`` tier has no budgets at all — approximation disabled.
    """

    name: str
    mode: str  # engine mode deployed at this tier ("exact" disables)
    budgets: tuple = ()  # ((target, ErrorBudget), ...)
    backend: str = "auto"
    description: str = ""

    @property
    def targets(self) -> tuple:
        return tuple(t for t, _ in self.budgets)


@dataclasses.dataclass(frozen=True)
class QualityConfig:
    """A tier resolved against a bit-width: one LayerQuality per target."""

    tier: str
    n: int
    order: int
    mode: str
    backend: str
    per_target: tuple  # of LayerQuality

    @property
    def targets(self) -> tuple:
        return tuple(q.target for q in self.per_target)

    def describe(self) -> str:
        if not self.per_target:
            return f"tier {self.tier}: exact (approximation disabled)"
        cells = ", ".join(
            f"{q.target}(n={q.n}, t={q.t}, {q.mode or self.mode})"
            for q in self.per_target
        )
        return f"tier {self.tier}: {cells} [{self.backend}]"


_TIERS: dict[str, QualityTier] = {}


def register_tier(tier: QualityTier) -> QualityTier:
    if tier.name in _TIERS:
        raise ValueError(f"tier {tier.name!r} is already registered")
    _TIERS[tier.name] = tier
    return tier


def get_tier(name: Union[str, QualityTier]) -> QualityTier:
    if isinstance(name, QualityTier):
        return name
    try:
        return _TIERS[name]
    except KeyError:
        raise ValueError(
            f"unknown quality tier {name!r}; registered tiers: {list_tiers()}"
        ) from None


def list_tiers() -> list[str]:
    return sorted(_TIERS)


# Budgets are on the NMED scale (strictly increasing in t, so each budget
# selects a unique t_max per bit-width).  At the default n=8 these
# resolve to: high -> mlp/moe t=2, attn t=1; balanced -> mlp/moe t=4
# (the old hardcoded default), attn t=2; draft -> delay-optimal t=4 with
# the O(1) inject surrogate.  The resolutions are pinned by tests.
register_tier(QualityTier(
    name="exact",
    mode="exact",
    description="no approximation (baseline quality)",
))
register_tier(QualityTier(
    name="high",
    mode="bitexact",
    budgets=(
        ("mlp", ErrorBudget(max_nmed=2e-3)),
        ("moe", ErrorBudget(max_nmed=2e-3)),
        ("attn", ErrorBudget(max_nmed=1e-3)),
    ),
    description="tight NMED budget; short splits, attention tightest",
))
register_tier(QualityTier(
    name="balanced",
    mode="bitexact",
    budgets=(
        ("mlp", ErrorBudget(max_nmed=1e-2)),
        ("moe", ErrorBudget(max_nmed=1e-2)),
        ("attn", ErrorBudget(max_nmed=2e-3)),
    ),
    description="the paper's working point: delay-optimal mlp split at n=8",
))
register_tier(QualityTier(
    name="draft",
    mode="inject",
    budgets=(
        ("mlp", ErrorBudget(max_nmed=5e-2)),
        ("moe", ErrorBudget(max_nmed=5e-2)),
    ),
    description="loose budget, moment-matched injection (throughput first)",
))


def resolve_tier(
    tier: Union[str, QualityTier],
    *,
    n: int = DEFAULT_N,
    order: int = 1,
) -> QualityConfig:
    """Resolve a tier's budgets into concrete per-target (n, t) selections.

    Each selection passes through :func:`resolve_t` with the tier's mode,
    so every (n, t) a tier hands out is statically certified.
    """
    spec = get_tier(tier)
    per_target = tuple(
        LayerQuality(
            target=target,
            n=n,
            t=resolve_t(n, budget, order=order, mode=spec.mode).t,
            mode=spec.mode,
            backend=spec.backend,
        )
        for target, budget in spec.budgets
    )
    return QualityConfig(
        tier=spec.name, n=n, order=order, mode=spec.mode,
        backend=spec.backend, per_target=per_target,
    )


@functools.lru_cache(maxsize=64)
def tier_cycle_factor(
    tier: Optional[str],
    *,
    n: int = DEFAULT_N,
    order: int = 1,
) -> float:
    """Relative per-cycle cost of serving at ``tier`` vs the exact design.

    The mean segmented critical path over the tier's resolved per-target
    splits, normalized by the accurate multiplier's ripple delay — i.e.
    ``mean(segmented_delay(n, t_target)) / ripple_delay(n)`` with every
    ``t_target`` chosen by :func:`resolve_tier`'s controller.  ``exact``
    (or ``None``) is the ripple design itself: factor 1.0.

    This is the gate-delay model's answer to "how much faster is one
    decode step at this tier", and it is what the serving layer's
    deterministic virtual clock charges per step (``repro.serve``): a
    cheaper tier genuinely shortens virtual step time, so SLO-adaptive
    tier degradation buys real (modeled) throughput.  At n=8 the
    registered tiers come out monotone: exact 1.0 > high > balanced >
    draft — pinned by tests.
    """
    if tier is None:
        return 1.0
    qc = resolve_tier(tier, n=n, order=order)
    if not qc.per_target:  # exact: approximation disabled
        return 1.0
    mean_delay = sum(segmented_delay(q.n, q.t) for q in qc.per_target)
    mean_delay /= len(qc.per_target)
    return mean_delay / ripple_delay(n)


# ------------------------------------------------- self-speculative decoding
@functools.lru_cache(maxsize=256)
def accept_rate_estimate(
    draft_tier: Union[str, QualityTier],
    verify_tier: Union[str, QualityTier],
    *,
    n: int = DEFAULT_N,
    order: int = 1,
) -> float:
    """Closed-form lower bound on the draft-vs-verify agreement rate.

    Self-speculative decoding (``repro.serve.strategy``) runs the *same*
    weights at two tiers; a draft proposal is accepted when both tiers'
    greedy argmax agree.  The tiers differ only through their
    approximate multiplies, so per budgeted GEMM class the probability
    that *either* tier's multiply deviates from exact is union-bounded
    by the sum of the two resolved splits' Eq. 10 ER estimates
    (``sweep_t(n)[t-1].er_bound``); the product over classes of
    ``max(0, 1 - (er_d + er_v))`` lower-bounds the chance that every
    multiply in both forwards agrees with the exact computation — and
    two computations that each match exact match each other.  Argmax
    additionally absorbs deviations too small to reorder the top logit,
    so the *measured* accept rate sits at or above this estimate (the
    ``speculative`` benchmark suite gates exactly that inequality).

    Degenerate pairs resolve to 1.0: two tiers with identical resolved
    (mode, per-target) configurations run bit-identical forwards.
    """
    qd = resolve_tier(get_tier(draft_tier), n=n, order=order)
    qv = resolve_tier(get_tier(verify_tier), n=n, order=order)
    if (qd.mode, qd.per_target) == (qv.mode, qv.per_target):
        return 1.0

    def er(qc: QualityConfig, target: str) -> float:
        for q in qc.per_target:
            if q.target == target:
                return sweep_t(q.n, order=order)[q.t - 1].er_bound
        return 0.0  # unbudgeted target: exact at this tier

    targets = {q.target for q in qd.per_target} | {q.target for q in qv.per_target}
    est = 1.0
    for tgt in sorted(targets):
        est *= max(0.0, 1.0 - (er(qd, tgt) + er(qv, tgt)))
    return est


def expected_round_tokens(accept_rate: float, k: int) -> float:
    """Expected committed tokens of one speculative round at depth ``k``.

    Acceptance is a per-position Bernoulli(α) chain stopped at the first
    rejection, plus the verify step's own "bonus" token, so the round
    commits ``1 + accepted`` tokens with expectation
    ``(1 - α^(k+1)) / (1 - α)`` — the truncated geometric series —
    reaching ``k + 1`` exactly at α = 1.
    """
    if not 0.0 <= accept_rate <= 1.0:
        raise ValueError(f"accept_rate must be in [0, 1], got {accept_rate}")
    if k < 1:
        raise ValueError(f"speculation depth k must be >= 1, got {k}")
    if accept_rate >= 1.0:
        return float(k + 1)
    return (1.0 - accept_rate ** (k + 1)) / (1.0 - accept_rate)


def speculation_gain(
    draft_tier: Union[str, QualityTier],
    verify_tier: Union[str, QualityTier],
    k: int,
    *,
    n: int = DEFAULT_N,
    order: int = 1,
) -> float:
    """Modeled tokens-per-cost ratio of speculating vs plain verify decode.

    One speculative round costs ``k * f_draft + f_verify`` exact-step
    units on the gate-delay clock (:func:`tier_cycle_factor`) and
    commits ``E = expected_round_tokens(α, k)`` verify-quality tokens;
    plain decode buys one token per ``f_verify``.  The gain is
    ``E * f_verify / (k * f_draft + f_verify)`` — above 1.0 speculation
    is worth it, and at ``draft == verify`` it is exactly 1.0 with the
    degenerate α = 1 (the bound and the cost model agree that
    self-speculating against yourself is a no-op).
    """
    alpha = accept_rate_estimate(draft_tier, verify_tier, n=n, order=order)
    e_tokens = expected_round_tokens(alpha, k)
    f_d = tier_cycle_factor(get_tier(draft_tier).name, n=n, order=order)
    f_v = tier_cycle_factor(get_tier(verify_tier).name, n=n, order=order)
    return e_tokens * f_v / (k * f_d + f_v)


def best_spec_k(
    draft_tier: Union[str, QualityTier],
    verify_tier: Union[str, QualityTier],
    *,
    k_max: int = 8,
    n: int = DEFAULT_N,
    order: int = 1,
) -> tuple[int, float]:
    """The controller's pick of speculation depth: ``(k, gain)`` maximizing
    :func:`speculation_gain` over ``1 <= k <= k_max`` (ties toward the
    smaller, lower-variance depth).  Callers treat ``gain <= 1`` as
    "don't speculate"."""
    if k_max < 1:
        raise ValueError(f"k_max must be >= 1, got {k_max}")
    best = (1, speculation_gain(draft_tier, verify_tier, 1, n=n, order=order))
    for k in range(2, k_max + 1):
        g = speculation_gain(draft_tier, verify_tier, k, n=n, order=order)
        if g > best[1]:
            best = (k, g)
    return best


def apply_quality(
    cfg: ModelConfig,
    tier: Union[str, QualityTier],
    *,
    n: int = DEFAULT_N,
    order: int = 1,
) -> ModelConfig:
    """Deploy a quality tier onto a model config.

    The ``exact`` tier (no budgets) disables approximation outright.
    Otherwise every budgeted target gets its controller-resolved
    :class:`LayerQuality` as an ``ApproxConfig`` override, so the dense /
    attention / MoE call sites each run their own (n, t, mode, backend)
    — the per-layer(-class) selection the paper's accuracy
    configurability promises.
    """
    qc = resolve_tier(tier, n=n, order=order)
    if not qc.per_target:
        return dataclasses.replace(cfg, approx=ApproxConfig(enabled=False))
    from repro.engine import modes as engine_modes  # lazy: avoid heavy import

    engine_modes.get_mode(qc.mode)
    base = qc.per_target[0]
    return dataclasses.replace(cfg, approx=ApproxConfig(
        enabled=True,
        n=base.n,
        t=base.t,
        fix_to_1=cfg.approx.fix_to_1,
        mode=qc.mode,
        rank=cfg.approx.rank,
        targets=qc.targets,
        backend=qc.backend,
        overrides=qc.per_target,
    ))
