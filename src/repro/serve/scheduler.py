"""Continuous-batching scheduler: slot-based admission, per-row retirement.

The serving core the ROADMAP's "heavy traffic" north star asks for.  A
fixed pool of ``batch_size`` *slots* shares one physical KV cache of
``prompt_len + max_new`` entries per slot; the decode step is jitted once
for the full pool and every global step advances all live rows together.
The continuous part is the slot lifecycle:

  queued -> admitted -> decoding -> retired -> (slot reused)

* **Admission** runs a single-row prefill of the new request (left-padded
  into the fixed prompt bucket, with *true* per-row position ids so pads
  are masked out of the cache) and scatters the resulting row cache into
  the pool cache at the free slot — surviving rows are untouched: no
  re-prefill, no re-batch barrier.
* **Decode** passes per-row position vectors (true position and physical
  write slot per row) to :func:`repro.train.steps.make_decode_step`, so
  rows sitting at different depths advance in one step.
* **Retirement** happens the step a row hits its budget or EOS; the freed
  slot is refilled from the queue before the next decode step.  A static
  batch, by contrast, burns dead decode steps on finished rows until the
  whole batch drains — that difference is the ``serve_throughput``
  benchmark's speedup column.

``static_serve_loop`` is the pre-continuous static-batch loop, kept as
the measured baseline and the parity oracle (it is exactly the old
``launch.serve`` behavior, request-list interface aside).

:meth:`ContinuousScheduler.run` drives the slot pool in either of two
loop modes.  *Closed loop* (the default) drains the queue as fast as
slots free — the historical behavior, bit for bit.  *Open loop*
(``arrivals_s=...``) gates admission on each request's arrival clock
and consults a pluggable :mod:`repro.serve.policy` admission policy per
tick, so queueing delay, burst backpressure, load shedding, and
SLO-adaptive accuracy-tier switching become first-class, measurable
behaviors (docs/serving.md §Admission policies).

Scope: decoder-only families.  Per-row position masking is exact for
attention caches, and SSD layers (``ssd``, ``attn_ssd``) zero their pads
out of the conv and SSM state, so both admit padded prompts; the row's
conv and SSM state are seated with its KV by the same scatter.  RG-LRU
layers still integrate left pads into their state, so admitting a padded
prompt for them is rejected (serve those with buckets equal to the true
prompt length).  A decode strategy that rolls back rejected tokens
cannot undo a recurrent state update, and is refused for any layer with
recurrent state.  Encoder-decoder configs are rejected at construction.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.policy import AdmissionPolicy, LoadSnapshot, StaticTier, get_policy
from repro.serve.request import Request, RequestStats
from repro.serve.stats import ServeResult, ServeStats, SlotAccounting, SpanTotals, span
from repro.serve.strategy import RowView, TierEngine, build_tier_engine, get_strategy
from repro.train.steps import make_decode_step, make_prefill_step

__all__ = [
    "ContinuousScheduler",
    "continuous_serve_loop",
    "static_serve_loop",
    "supports_continuous",
]

# Decode internals that used to live here as private closures/classes and
# now belong to repro.serve.strategy.  Importing them from this module was
# never supported API; raise with a pointer instead of silently breaking
# (docs/engine.md §Migration map has the closure -> strategy mapping).
_MOVED_TO_STRATEGY = {
    "_TierEngine": "TierEngine",
    "_build_engine": "build_tier_engine",
    "decode_greedy": "GreedyDecode.decode_round",
    "seat": "ContinuousScheduler.run (scheduler-internal)",
    "retire": "ContinuousScheduler.run (scheduler-internal)",
    "pump": "ContinuousScheduler.run (scheduler-internal)",
}


def __getattr__(name):
    if name in _MOVED_TO_STRATEGY:
        raise AttributeError(
            f"repro.serve.scheduler.{name} moved to the decode-strategy "
            f"layer: use repro.serve.strategy.{_MOVED_TO_STRATEGY[name]} "
            f"(see docs/engine.md, 'Scheduler closures -> DecodeStrategy')"
        )
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

# layer kinds whose state folds in every token it sees (no rollback)
STATEFUL_KINDS = ("rglru", "ssd", "attn_ssd")
# of those, the kinds whose prefill cannot mask left pads out of the state
PAD_ABSORBING_KINDS = ("rglru",)


def absorbs_pads(cfg) -> bool:
    """Whether some layer of ``cfg`` would absorb left pads into its state."""
    return any(k in PAD_ABSORBING_KINDS for k in cfg.layer_pattern)


def supports_continuous(cfg) -> bool:
    """Whether the continuous scheduler fully supports ``cfg`` — including
    padded admission of mixed-length prompts.  One predicate shared by the
    scheduler's own checks and the CLI's auto-selection, so they cannot
    drift: attention and SSD decoder stacks qualify; encoder-decoder
    configs are rejected at construction and RG-LRU stacks reject padded
    admission."""
    return not cfg.is_encdec and not absorbs_pads(cfg)


def _apply_pool_quality(model, quality):
    """Resolve an accuracy tier into this pool's engine config: the
    ``engine.config`` controller picks each GEMM class's cheapest valid
    splitting point and the model is rebuilt on the resulting config
    (parameters are unaffected — approximation only changes the forward
    math).  Returns ``(model, canonical_tier_name)``."""
    if quality is None:
        return model, None
    from repro.engine import config as engine_config
    from repro.models.registry import build_model

    tier = engine_config.get_tier(quality)
    return build_model(engine_config.apply_quality(model.cfg, tier)), tier.name


def _check_request_quality(req: Request, pool_tier) -> None:
    """A request sold at a tier must be served by a pool resolved to that
    tier — mismatches raise at admission instead of silently serving the
    request at a different accuracy."""
    if req.quality is None:
        return
    from repro.engine.config import get_tier

    want = get_tier(req.quality).name
    if pool_tier is None:
        raise ValueError(
            f"request {req.id} demands quality tier {want!r}, but this pool "
            f"was built without one (pass quality={want!r}, or run one pool "
            f"per tier)"
        )
    if want != pool_tier:
        raise ValueError(
            f"request {req.id} demands quality tier {want!r}, but this pool "
            f"serves {pool_tier!r}; run one pool per tier"
        )


def _scatter_row(big: dict, small: dict, row) -> dict:
    """Write the single-row cache pytree ``small`` into row ``row`` of ``big``.

    Leaf layout follows ``transformer.init_caches``: ``scan`` leaves carry
    the batch on axis 1 (stacked layer groups first), ``rem`` leaves on
    axis 0.  Jitted with the pool cache donated, this is the admission
    primitive — one scatter, surviving rows untouched.
    """
    row = jnp.asarray(row, jnp.int32)

    def scat(axis):
        def f(b, s):
            starts = [jnp.int32(0)] * b.ndim
            starts[axis] = row
            return jax.lax.dynamic_update_slice(b, s.astype(b.dtype), tuple(starts))

        return f

    out = dict(big)
    if "scan" in big:
        out["scan"] = jax.tree_util.tree_map(scat(1), big["scan"], small["scan"])
    if "rem" in big:
        out["rem"] = jax.tree_util.tree_map(scat(0), big["rem"], small["rem"])
    return out


@dataclasses.dataclass
class _Slot:
    """Host-side state of one live row."""

    req: Request
    tokens: list  # generated token ids (first from admission prefill)
    admit_step: int
    done: bool = False
    finish_reason: str = ""
    arrival_s: float = 0.0  # open loop: arrival time on the run clock
    queue_delay_s: Optional[float] = None  # open loop: admission - arrival
    tier_served: str = ""  # accuracy tier at admission ("" = pool config)
    proposed: int = 0  # speculative: draft tokens proposed for this row
    accepted: int = 0  # speculative: draft tokens the verify step accepted
    # run clock (seconds from run start) of each token: the first is the
    # time to first token, the last the retirement once done
    stamps: list = dataclasses.field(default_factory=list)

    @property
    def emitted(self) -> int:
        return len(self.tokens)

    def absorb(self, tok: int, now: float) -> None:
        """Take one token, stamped ``now`` on the run clock (the open
        loop's clock, or perf_counter - t0)."""
        self.tokens.append(tok)
        self.stamps.append(now)
        if self.req.eos_id is not None and tok == self.req.eos_id:
            self.done, self.finish_reason = True, "eos"
        elif self.emitted >= self.req.max_new:
            self.done, self.finish_reason = True, "budget"


class ContinuousScheduler:
    """Slot-pool continuous-batching scheduler over one model + params.

    Args:
      model, params: a built decoder-only model and its parameters.
      batch_size: number of slots (the jitted decode batch).
      prompt_len: prompt bucket width; every prompt (<= prompt_len) is
        left-padded to it so admission prefill compiles once.
      max_new: per-slot generation capacity (request budgets must fit).
      mesh: optional device mesh (e.g. ``sharding.data_parallel_mesh()``)
        installed around every jitted call — the model's internal
        ``constrain`` rules then shard the pool batch over the data axis.
      quality: optional accuracy tier (a ``repro.engine.config`` tier
        name or ``QualityTier``).  The tier is resolved to a per-run
        engine config — the controller picks each GEMM class's cheapest
        splitting point meeting the tier's error budget — and the model
        is rebuilt on that config; the decode/prefill steps jit once
        against it.  Requests carrying a ``quality`` are checked against
        the pool's tier at admission: a mismatch raises rather than
        silently serving the request at a different accuracy.
      strategy: the pool's decode discipline — a
        :mod:`repro.serve.strategy` name (``"greedy"`` / ``"speculative"``)
        or a :class:`~repro.serve.strategy.DecodeStrategy` instance.
        ``GreedyDecode`` (the default) reproduces the pre-strategy
        scheduler bit for bit; ``SelfSpeculative`` reserves
        ``strategy.extra_capacity`` spare physical KV slots per row for
        its verify window, admits at its verify tier, and commits
        1..k+1 verify-quality tokens per round.
    """

    def __init__(self, model, params, *, batch_size: int, prompt_len: int,
                 max_new: int, mesh=None, quality=None, strategy=None):
        if model.cfg.is_encdec:
            raise ValueError(
                "ContinuousScheduler supports decoder-only families; "
                "serve encoder-decoder configs with static_serve_loop"
            )
        if batch_size < 1 or prompt_len < 1 or max_new < 1:
            raise ValueError("batch_size, prompt_len and max_new must be >= 1")
        model, self.quality = _apply_pool_quality(model, quality)
        # RG-LRU layers integrate left pads into their state (positions
        # cannot mask them out), so padded admission would be silently
        # wrong — enforced per request in _pad
        self._absorbs_pads = absorbs_pads(model.cfg)
        self.model, self.params = model, params
        self.batch_size, self.prompt_len, self.max_new = batch_size, prompt_len, max_new
        self.strategy = get_strategy(strategy)
        stateful = sorted({k for k in model.cfg.layer_pattern if k in STATEFUL_KINDS})
        if self.strategy.rolls_back and stateful:
            raise ValueError(
                f"decode strategy {self.strategy.name!r} rolls back rejected tokens, "
                f"but {model.cfg.name} has layers with recurrent state ({stateful}) "
                f"that a rollback cannot undo; serve it with the greedy strategy"
            )
        # physical per-row cache: the logical window plus whatever spare
        # tail the strategy needs (speculative verify writes up to k past
        # the last committed slot before rollback)
        self.capacity = prompt_len + max_new + self.strategy.extra_capacity
        self.mesh = mesh
        self._cache_dtype = jnp.dtype(model.cfg.dtype)
        self._engines: dict = {}
        self._base_engine = self._build_engine(model, self.quality, self.quality)
        self._engines[self.quality] = self._base_engine
        # the pool tier's steps under their historical names — warmup and
        # external callers target the base engine
        self._admit_step = self._base_engine.admit_step
        self._prefill_pool = self._base_engine.prefill_pool
        self._decode = self._base_engine.decode

    # ------------------------------------------------------------- engines
    def _build_engine(self, model, name, key) -> TierEngine:
        """Jit the (admit, pool-prefill, decode, verify) bundle for one
        tier — the heavy lifting lives in
        :func:`repro.serve.strategy.build_tier_engine`."""
        return build_tier_engine(
            model, self.capacity, name=name, key=key, scatter_row=_scatter_row,
        )

    def engine_for(self, tier) -> TierEngine:
        """The engine serving ``tier`` (None = the pool's base config),
        built and jitted on first visit, cached for the scheduler's
        lifetime.  Safe to apply to the already-tier-resolved pool model:
        ``engine.config.apply_quality`` replaces the approx config
        wholesale, so re-tiering is not cumulative.  Decode strategies
        call this to reach their draft/verify tiers."""
        key = tier if tier is not None else self.quality
        eng = self._engines.get(key)
        if eng is None:
            model, name = _apply_pool_quality(self.model, key)
            eng = self._build_engine(model, name, key)
            self._engines[key] = eng
        return eng

    # pre-strategy private name, kept for callers that grew around it
    _engine_for = engine_for

    # ------------------------------------------------------------- helpers
    def _mesh_ctx(self):
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.distributed.sharding import mesh_context

        return mesh_context(self.mesh)

    def _pad(self, req: Request) -> tuple:
        """Left-pad one prompt into the bucket; true position ids for pads < 0.

        Tier-tag enforcement moved to the admission paths in :meth:`run`
        (it is policy-dependent now: an SLO-adaptive policy treats the
        tag as a preference, not a contract)."""
        ln = req.prompt_len
        if ln > self.prompt_len:
            raise ValueError(
                f"request {req.id}: prompt length {ln} exceeds bucket {self.prompt_len}"
            )
        if req.max_new > self.max_new:
            raise ValueError(
                f"request {req.id}: budget {req.max_new} exceeds slot capacity {self.max_new}"
            )
        if self._absorbs_pads and ln < self.prompt_len:
            raise ValueError(
                f"request {req.id}: prompt length {ln} < bucket {self.prompt_len}, "
                f"but {self.model.cfg.name} has recurrent-state (RG-LRU) layers that "
                f"would integrate the left pads (positions cannot mask their state); "
                f"use a bucket equal to the prompt length, or pad prompts upstream"
            )
        toks = np.zeros((self.prompt_len,), np.int32)
        toks[self.prompt_len - ln:] = req.tokens
        pos = np.arange(self.prompt_len, dtype=np.int32) - (self.prompt_len - ln)
        return toks, pos

    def _prefill_row(self, req: Request, caches: dict, row: int, engine=None):
        """Fused admission: single-row prefill + scatter; returns (caches, tok0)."""
        eng = engine if engine is not None else self._base_engine
        with span("admit.dispatch"):
            toks, pos = self._pad(req)
            caches, tok0 = eng.admit_step(
                self.params, caches, jnp.asarray(toks[None]),
                jnp.asarray(pos[None]), jnp.int32(row),
            )
        with span("admit.sync"):
            return caches, int(np.asarray(tok0))

    def warmup(self) -> None:
        """Compile the pool prefill, the admission step, and the pool decode."""
        B = self.batch_size
        caches = self.model.init_caches(B, self.capacity, self._cache_dtype)
        with self._mesh_ctx():
            toks = jnp.zeros((B, self.prompt_len), jnp.int32)
            pos = jnp.broadcast_to(
                jnp.arange(self.prompt_len, dtype=jnp.int32)[None], toks.shape
            )
            caches, _ = self._prefill_pool(self.params, toks, pos)
            req = Request(id=-1, tokens=np.zeros(1, np.int32), max_new=1)
            caches, _ = self._prefill_row(req, caches, 0)
            zeros = jnp.zeros((B,), jnp.int32)
            nxt, caches = self._decode(
                self.params, caches, jnp.zeros((B, 1), jnp.int32), zeros, zeros,
            )
            jax.block_until_ready(nxt)
            self.strategy.warmup(self)

    # ----------------------------------------------------------------- run
    def run(
        self,
        requests: Sequence[Request],
        *,
        warmup: bool = True,
        arrivals_s: Optional[Sequence[float]] = None,
        policy=None,
        step_time_s: float = 0.01,
        clock: str = "virtual",
    ) -> ServeResult:
        """Serve ``requests`` to completion; returns stats + token streams.

        **Closed loop** (default, ``arrivals_s=None``): the queue is
        drained as fast as slots free up — the pre-policy behavior, bit
        for bit (the implicit :class:`~repro.serve.policy.StaticTier`
        admits everything at the pool's tier through the same jitted
        steps, and all timing keeps the legacy run-start semantics).

        **Open loop** (``arrivals_s`` given — one non-decreasing arrival
        time per request, seconds from run start): a request becomes
        admissible only once the clock passes its arrival time, so
        queueing delay and burst backpressure are *measured* instead of
        assumed away.  Per-request ``ttft_s``/``latency_s`` are re-based
        to arrival, and ``queue_delay_s`` separates out the waiting
        component.  ``clock`` selects the timebase:

        * ``"virtual"`` (default) — deterministic modeled time: every
          admission prefill and pool decode step advances the clock by
          ``step_time_s`` scaled by the serving tier's
          :func:`repro.engine.config.tier_cycle_factor` (the paper's
          gate-delay model: cheaper tiers take genuinely shorter
          virtual steps, exact = 1.0).  Identical traces replay
          identical timings, so queue delays, SLO attainment, and
          tier-switch sequences are reproducible and CI-gateable.
        * ``"wall"`` — real time; idle gaps are slept through.

        Host spans (:func:`repro.serve.stats.span`) cover each loop
        ``tick`` and what runs inside it; their per-run totals land in
        ``ServeStats.spans``, and every request's per-token stamps in
        ``RequestStats.token_s``.

        ``policy`` is an :class:`~repro.serve.policy.AdmissionPolicy`
        instance or registry name (``"static"``/``"slo-adaptive"``/
        ``"reject"``).  Once per scheduler tick the policy picks the
        serving tier — admissions *and* decode run at it, pool-wide,
        the software analogue of reconfiguring the multipliers'
        splitting point in place — and per queued request it decides
        admit vs shed.  Tier switches reuse the one KV cache
        (approximation never changes cache shapes); each newly visited
        tier jits its step functions on first use.
        """
        open_loop = arrivals_s is not None
        pol = get_policy(policy) if policy is not None else StaticTier()
        if open_loop:
            arrivals = [float(a) for a in arrivals_s]
            if len(arrivals) != len(requests):
                raise ValueError(
                    f"arrivals_s has {len(arrivals)} entries for "
                    f"{len(requests)} requests"
                )
            if any(b < a for a, b in zip(arrivals, arrivals[1:])):
                raise ValueError("arrivals_s must be non-decreasing")
            if step_time_s <= 0:
                raise ValueError(f"step_time_s must be > 0, got {step_time_s}")
            if clock not in ("virtual", "wall"):
                raise ValueError(
                    f"clock must be 'virtual' or 'wall', got {clock!r}"
                )
        if warmup:
            self.warmup()
        B, P = self.batch_size, self.prompt_len
        pending: collections.deque = collections.deque(
            zip(requests, arrivals) if open_loop else ()
        )
        queue: collections.deque = collections.deque(
            () if open_loop else requests
        )
        arrived_at: dict = {}  # id -> arrival time, while queued (open loop)
        slots: list[Optional[_Slot]] = [None] * B
        retired: list[RequestStats] = []
        rejected: list[RequestStats] = []
        outputs: dict = {}
        cur_tok = np.zeros((B, 1), np.int32)
        prefill_s = decode_s = 0.0
        step = 0
        busy_row_steps = 0
        # slot-accounting ledger (see stats.SlotAccounting): counted as the
        # loop runs, so the soak harness audits the scheduler itself rather
        # than re-deriving "what must have happened" from the retired list
        seated_total = 0
        pool_seats = 0
        admission_seats = 0
        max_live = 0
        seat_counts = [0] * B
        last_write = [0] * B  # per-slot last physical KV write index
        position_violations = 0
        spec_rounds = spec_proposed = spec_accepted = 0
        modeled_cost = 0.0  # sum of round costs in exact-decode-step units
        engine = self._base_engine
        # admissions (prefill) run at the strategy's admission tier — for
        # greedy that is the serving engine itself; a speculative strategy
        # pins it to its verify tier so the cache prefix is verify-quality
        admit_eng = self.engine_for(self.strategy.admission_key(engine.key))
        pol.begin(self.quality)
        now = 0.0  # open-loop clock (virtual seconds, or wall since t0)

        t0 = time.perf_counter()
        spans = SpanTotals()  # host spans of this run (stats.span)

        def run_clock() -> float:
            # the run clock: the open loop's own (virtual or wall), else
            # wall seconds since t0
            return now if open_loop else time.perf_counter() - t0

        def pump() -> None:
            # open loop: requests whose arrival time has passed move from
            # the pending stream into the admissible queue
            while pending and pending[0][1] <= now + 1e-12:
                req, arr = pending.popleft()
                arrived_at[req.id] = arr
                queue.append(req)

        def snapshot() -> LoadSnapshot:
            head_wait = 0.0
            if open_loop and queue:
                head_wait = now - arrived_at[queue[0].id]
            return LoadSnapshot(
                now_s=run_clock(),
                step=step,
                queue_depth=len(queue),
                pending=len(pending),
                live_rows=sum(1 for s in slots if s is not None),
                batch_size=B,
                head_wait_s=head_wait,
            )

        def retire(i: int) -> None:
            s = slots[i]
            rs = RequestStats(
                id=s.req.id,
                prompt_len=s.req.prompt_len,
                tokens_out=s.emitted,
                admit_step=s.admit_step,
                # what the client experiences: open loop, both re-based to
                # arrival; closed loop, every request arrives at run start
                ttft_s=s.stamps[0] - s.arrival_s,
                latency_s=(s.stamps[-1] if s.done else run_clock()) - s.arrival_s,
                finish_reason=s.finish_reason,
                arrival_s=s.arrival_s,
                queue_delay_s=s.queue_delay_s,
                tier_served=s.tier_served,
                slo_ttft_s=s.req.slo_ttft_s,
                proposed=s.proposed,
                accepted=s.accepted,
                token_s=tuple(s.stamps),
            )
            retired.append(rs)
            outputs[s.req.id] = np.asarray(s.tokens, np.int32)
            slots[i] = None
            pol.observe(rs)

        def reject(req: Request) -> None:
            if open_loop:
                arr = arrived_at.pop(req.id)
                rs = RequestStats(
                    id=req.id, prompt_len=req.prompt_len, tokens_out=0,
                    admit_step=step, ttft_s=0.0, latency_s=now - arr,
                    finish_reason="rejected", arrival_s=arr,
                    queue_delay_s=now - arr, slo_ttft_s=req.slo_ttft_s,
                )
            else:
                rs = RequestStats(
                    id=req.id, prompt_len=req.prompt_len, tokens_out=0,
                    admit_step=step, ttft_s=0.0,
                    latency_s=time.perf_counter() - t0,
                    finish_reason="rejected", slo_ttft_s=req.slo_ttft_s,
                )
            rejected.append(rs)

        def seat(i: int, req: Request, tok0: int, t_first: float,
                 *, pool: bool = False, arrival: float = 0.0,
                 queue_delay: Optional[float] = None) -> None:
            nonlocal seated_total, pool_seats, admission_seats
            seated_total += 1
            seat_counts[i] += 1
            if pool:
                pool_seats += 1
            else:
                admission_seats += 1
            # admission prefill wrote cache indices [0, P); the row's first
            # decode write lands at exactly P
            last_write[i] = P - 1
            slot = _Slot(req=req, tokens=[], admit_step=step,
                         arrival_s=arrival, queue_delay_s=queue_delay,
                         tier_served=admit_eng.name or "")
            slot.absorb(tok0, t_first)
            cur_tok[i, 0] = tok0
            slots[i] = slot
            if slot.done:  # budget 1 / instant EOS: free the slot again
                retire(i)

        with self._mesh_ctx(), spans.active():
            if open_loop:
                if clock == "wall":
                    now = time.perf_counter() - t0
                pump()
            with span("tick"):  # the initial fill is a tick of its own
                if (
                    not open_loop
                    and len(queue) >= B
                    # only when the policy cannot shed (admit is the base
                    # always-True implementation) — a shedding policy must
                    # see every request through the per-request admission
                    # path
                    and type(pol).admit is AdmissionPolicy.admit
                ):
                    # initial fill: the batched prefill of all B slots *is*
                    # the pool cache — one dispatch, no scatters
                    with span("pool_prefill"):
                        first = [queue.popleft() for _ in range(B)]
                        if pol.enforces_tier_tags:
                            for r in first:
                                _check_request_quality(r, self.quality)
                        padded = [self._pad(r) for r in first]
                        toks = jnp.asarray(np.stack([t for t, _ in padded]))
                        pos = jnp.asarray(np.stack([p for _, p in padded]))
                        caches, tok0s = admit_eng.prefill_pool(self.params, toks, pos)
                        with span("pool_prefill.sync"):
                            tok0s = np.asarray(tok0s)
                        t_b = time.perf_counter()
                        prefill_s += t_b - t0
                        for i, req in enumerate(first):
                            seat(i, req, int(tok0s[i]), t_b - t0, pool=True)
                else:
                    caches = self.model.init_caches(B, self.capacity, self._cache_dtype)
            state_bytes = self.model.state_bytes(caches)
            while True:
                with span("tick"):
                    if open_loop:
                        if clock == "wall":
                            now = time.perf_counter() - t0
                        pump()
                    # one control tick: the policy picks this tick's serving
                    # tier; admissions and decode below both run at it
                    with span("policy"):
                        want = pol.tier(snapshot())
                        want = want if want is not None else self.quality
                        if want != engine.key:
                            engine = self.engine_for(want)
                            admit_eng = self.engine_for(
                                self.strategy.admission_key(engine.key))
                    # retire finished rows, refill freed slots from the queue
                    for i in range(B):
                        if slots[i] is not None and slots[i].done:
                            retire(i)
                        while slots[i] is None and queue:
                            req = queue[0]
                            if not pol.admit(req, snapshot()):
                                queue.popleft()
                                reject(req)
                                continue
                            queue.popleft()
                            if pol.enforces_tier_tags:
                                _check_request_quality(req, self.quality)
                            with span("admit"):
                                t_a = time.perf_counter()
                                caches, tok0 = self._prefill_row(req, caches, i, admit_eng)
                                t_b = time.perf_counter()
                                prefill_s += t_b - t_a
                                if open_loop:
                                    arr = arrived_at.pop(req.id)
                                    qd = now - arr
                                    now = (
                                        now + step_time_s * admit_eng.cost_factor
                                        if clock == "virtual"
                                        else time.perf_counter() - t0
                                    )
                                    seat(i, req, tok0, now, arrival=arr, queue_delay=qd)
                                    pump()  # admission took time: new arrivals?
                                else:
                                    seat(i, req, tok0, t_b - t0)

                    live = [i for i in range(B) if slots[i] is not None]
                    if not live:
                        if open_loop and pending:
                            # idle gap: nothing decoding, nothing admissible —
                            # jump (or sleep) the clock to the next arrival
                            with span("idle"):
                                nxt_arrival = pending[0][1]
                                if clock == "virtual":
                                    now = max(now, nxt_arrival)
                                else:
                                    wait = nxt_arrival - (time.perf_counter() - t0)
                                    if wait > 0:
                                        time.sleep(wait)
                                    now = time.perf_counter() - t0
                            pump()
                            continue
                        break
                    max_live = max(max_live, len(live))

                    # one decode round, delegated to the pool's strategy:
                    # greedy is exactly the historical single decode;
                    # speculative is k draft steps + one batched verify forward
                    rows = [
                        RowView(index=i, prompt_len=slots[i].req.prompt_len,
                                emitted=slots[i].emitted,
                                strategy=slots[i].req.strategy)
                        for i in live
                    ]
                    with span("decode"):
                        t_d = time.perf_counter()
                        rr = self.strategy.decode_round(
                            self, engine, caches, cur_tok, rows,
                            speculate=pol.speculation(snapshot()),
                        )
                        decode_s += time.perf_counter() - t_d
                    caches = rr.caches
                    step += rr.steps
                    busy_row_steps += len(live) * rr.steps
                    modeled_cost += rr.cost
                    spec_proposed += rr.proposed
                    spec_accepted += rr.accepted
                    if rr.proposed:
                        spec_rounds += 1
                    with span("absorb"):
                        if open_loop:
                            now = (
                                now + step_time_s * rr.cost
                                if clock == "virtual"
                                else time.perf_counter() - t0
                            )
                        stamp = run_clock()  # one stamp for the round's tokens
                        for i in live:
                            s = slots[i]
                            pr = rr.per_row.get(i)
                            if pr is not None:
                                s.proposed += pr[0]
                                s.accepted += pr[1]
                            for tok in rr.tokens.get(i, ()):
                                if s.done:  # budget/EOS cut the committed run short
                                    break
                                # per committed token the same invariants the
                                # pre-strategy loop checked per step: the
                                # physical write index advances by exactly one
                                # slot, stays inside the logical window, and
                                # the true position is the write index shifted
                                # by the row's pad offset
                                wr = P + s.emitted - 1
                                pp = s.req.prompt_len + s.emitted - 1
                                if (
                                    wr != last_write[i] + 1
                                    or wr >= P + self.max_new
                                    or pp != wr - (P - s.req.prompt_len)
                                ):
                                    position_violations += 1
                                last_write[i] = wr
                                s.absorb(int(tok), stamp)
                            cur_tok[i, 0] = s.tokens[-1]
                        if open_loop:
                            pump()

        wall = time.perf_counter() - t0
        # SLO attainment over every *offered* request carrying an SLO:
        # rejected (and any starved) requests count as missed, so a
        # shedding policy cannot game the metric by refusing work
        slo_total = sum(
            1 for r in requests if r.slo_ttft_s is not None
        )
        slo_attained = sum(
            1 for r in retired
            if r.slo_ttft_s is not None and r.ttft_s <= r.slo_ttft_s
        )
        switches = pol.switches
        stats = ServeStats(
            requests=len(retired),
            tokens_out=sum(r.tokens_out for r in retired),
            wall_s=wall,
            prefill_s=prefill_s,
            decode_s=decode_s,
            batch_latencies_s=(),
            devices=len(jax.devices()),
            scheduler="continuous",
            decode_steps=step,
            slot_utilization=busy_row_steps / (B * step) if step else 1.0,
            ttft_s=tuple(r.ttft_s for r in retired),
            request_latencies_s=tuple(r.latency_s for r in retired),
            quality=self.quality or "",
            open_loop=open_loop,
            policy=pol.name,
            queue_delay_s=tuple(
                r.queue_delay_s for r in retired
                if r.queue_delay_s is not None
            ),
            tier_switches=len(switches),
            rejected=len(rejected),
            starved=len(requests) - len(retired) - len(rejected),
            slo_total=slo_total,
            slo_attained=slo_attained,
            strategy=self.strategy.name,
            spec_rounds=spec_rounds,
            spec_proposed=spec_proposed,
            spec_accepted=spec_accepted,
            modeled_cost=modeled_cost,
            spans=spans.totals(),
            state_bytes=state_bytes,
        )
        accounting = SlotAccounting(
            seated=seated_total,
            retired=len(retired),
            pool_prefill_seats=pool_seats,
            admission_seats=admission_seats,
            max_live=max_live,
            slot_reuse=tuple(seat_counts),
            position_violations=position_violations,
        )
        return ServeResult(stats=stats, request_stats=tuple(retired),
                           outputs=outputs, accounting=accounting,
                           tier_switches=switches, rejected=tuple(rejected))


def continuous_serve_loop(
    model, params, requests: Sequence[Request], *,
    batch_size: int, prompt_len: int, max_new: int,
    mesh=None, warmup: bool = True, quality=None, strategy=None, **run_kwargs,
) -> ServeResult:
    """One-shot convenience wrapper over :class:`ContinuousScheduler`.

    ``strategy`` selects the pool's decode discipline (a
    :mod:`repro.serve.strategy` name or instance); ``run_kwargs`` pass
    through to :meth:`ContinuousScheduler.run` (``arrivals_s`` /
    ``policy`` / ``step_time_s`` / ``clock`` for open-loop clocked
    admission)."""
    sched = ContinuousScheduler(
        model, params,
        batch_size=batch_size, prompt_len=prompt_len, max_new=max_new, mesh=mesh,
        quality=quality, strategy=strategy,
    )
    return sched.run(requests, warmup=warmup, **run_kwargs)


# -------------------------------------------------------------------- static
@functools.lru_cache(maxsize=8)
def _static_steps(model, max_seq: int, mem_len: int):
    """Jitted (prefill, decode) pair per (model, shapes) — cached so
    repeated static runs (benchmark best-of repeats) reuse the compiles."""
    return (
        jax.jit(make_prefill_step(model, max_seq, mem_len=mem_len)),
        jax.jit(make_decode_step(model), donate_argnums=1),
    )


def static_serve_loop(
    model, params, requests: Sequence[Request], *,
    batch_size: int, prompt_len: int, gen: int,
    seed: int = 0, warmup: bool = True, quality=None,
) -> ServeResult:
    """The pre-continuous static-batch loop, kept as baseline and oracle.

    Pops ``batch_size`` requests at a time, left-pads prompts into the
    shared bucket (all rows share the ``arange`` position ids — the
    legacy position approximation), decodes every batch to the *largest*
    budget in it, and only re-batches once the whole batch drains.
    Finished rows burn dead decode steps until then; ``tokens_out``
    counts useful (budget/EOS-bounded) tokens only, so the throughput
    numbers are directly comparable with the continuous scheduler's.
    ``quality`` resolves an accuracy tier exactly as the continuous
    scheduler does, so per-tier parity holds bit for bit.
    """
    model, pool_tier = _apply_pool_quality(model, quality)
    cfg = model.cfg
    max_seq = prompt_len + gen
    mem_len = prompt_len if cfg.is_encdec else 0
    try:
        prefill, decode = _static_steps(model, max_seq, mem_len)
    except TypeError:  # unhashable model/config: build fresh, uncached
        prefill = jax.jit(make_prefill_step(model, max_seq, mem_len=mem_len))
        decode = jax.jit(make_decode_step(model), donate_argnums=1)
    rng = np.random.default_rng(seed)  # encoder-memory synthesis only

    def make_batch(batch_reqs: list) -> dict:
        b = len(batch_reqs)
        toks = np.zeros((b, prompt_len), np.int32)
        for i, r in enumerate(batch_reqs):
            _check_request_quality(r, pool_tier)
            if r.prompt_len > prompt_len:
                raise ValueError(
                    f"request {r.id}: prompt length {r.prompt_len} exceeds bucket {prompt_len}"
                )
            if r.max_new > gen:
                raise ValueError(
                    f"request {r.id}: budget {r.max_new} exceeds gen {gen}"
                )
            toks[i, prompt_len - r.prompt_len:] = r.tokens
        batch = {"tokens": jnp.asarray(toks)}
        if cfg.is_encdec:
            batch["src_embeds"] = jnp.asarray(
                rng.standard_normal((b, prompt_len, cfg.d_model)), jnp.float32
            )
            batch["src_pos"] = jnp.arange(prompt_len, dtype=jnp.int32)[None].repeat(b, 0)
        return batch

    if warmup and requests:
        # compile every batch shape the loop will see: the full batch plus
        # the uneven remainder batch, so no XLA compile lands in the
        # timed region ("numbers measure scheduling, not compilation")
        shapes = {min(batch_size, len(requests))}
        if len(requests) > batch_size and len(requests) % batch_size:
            shapes.add(len(requests) % batch_size)
        for b0 in sorted(shapes):
            dummy = [Request(id=-1, tokens=np.zeros(1, np.int32), max_new=1)] * b0
            caches, logits = prefill(params, make_batch(dummy))
            tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            logits, caches = decode(params, caches, tok, jnp.int32(prompt_len))
            jax.block_until_ready(logits)

    queue = collections.deque(requests)
    retired: list[RequestStats] = []
    outputs: dict = {}
    prefill_s = decode_s = 0.0
    batch_latencies: list[float] = []
    total_steps = 0
    busy_row_steps = 0
    total_row_steps = 0
    max_live = 0

    t0 = time.perf_counter()
    while queue:
        t_batch = time.perf_counter()
        batch_reqs = [queue.popleft() for _ in range(min(batch_size, len(queue)))]
        max_live = max(max_live, len(batch_reqs))
        caches, logits = prefill(params, make_batch(batch_reqs))
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter()
        prefill_s += t_prefill - t_batch

        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        step_toks = [tok]  # device-side; materialized once per batch, so the
        t_first = time.perf_counter()  # decode loop dispatches async (pre-PR behavior)
        steps = min(gen, max(r.max_new for r in batch_reqs))
        for g in range(steps - 1):
            logits, caches = decode(params, caches, tok, jnp.int32(prompt_len + g))
            tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            step_toks.append(tok)
        jax.block_until_ready(tok)
        decode_s += time.perf_counter() - t_first
        host_toks = np.concatenate([np.asarray(t) for t in step_toks], axis=1)
        streams = [list(map(int, row)) for row in host_toks]
        total_steps += steps - 1
        t_end = time.perf_counter()
        batch_latencies.append(t_end - t_batch)

        for r, stream in zip(batch_reqs, streams):
            useful, reason = [], "budget"
            for t in stream[: r.max_new]:
                useful.append(t)
                if r.eos_id is not None and t == r.eos_id:
                    reason = "eos"
                    break
            # row r is live at decode step g iff it still needs token g+1:
            # steps past its useful length are the static batch's dead steps
            busy_row_steps += len(useful) - 1
            total_row_steps += steps - 1
            retired.append(RequestStats(
                id=r.id, prompt_len=r.prompt_len, tokens_out=len(useful),
                admit_step=0, ttft_s=t_first - t0, latency_s=t_end - t0,
                finish_reason=reason,
            ))
            outputs[r.id] = np.asarray(useful, np.int32)

    wall = time.perf_counter() - t0
    stats = ServeStats(
        requests=len(retired),
        tokens_out=sum(r.tokens_out for r in retired),
        wall_s=wall,
        prefill_s=prefill_s,
        decode_s=decode_s,
        batch_latencies_s=tuple(batch_latencies),
        devices=len(jax.devices()),
        scheduler="static",
        decode_steps=total_steps,
        slot_utilization=(
            busy_row_steps / total_row_steps if total_row_steps else 1.0
        ),
        ttft_s=tuple(r.ttft_s for r in retired),
        request_latencies_s=tuple(r.latency_s for r in retired),
        quality=pool_tier or "",
    )
    # the static loop has no slot pool: every request is seated by its
    # batch prefill and retired when the batch drains, so conservation is
    # structural — the ledger still reports it so soak audits run on both
    # schedulers with one code path
    accounting = SlotAccounting(
        seated=len(retired),
        retired=len(retired),
        pool_prefill_seats=len(retired),
        admission_seats=0,
        max_live=max_live,
        slot_reuse=(),
        position_violations=0,
    )
    return ServeResult(stats=stats, request_stats=tuple(retired),
                       outputs=outputs, accounting=accounting)
