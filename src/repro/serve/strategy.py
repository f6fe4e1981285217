"""Decode strategies: how a slot pool turns live rows into tokens.

The per-step decode logic used to live in nested closures inside
``ContinuousScheduler.run()``; this module extracts it into a small
strategy layer so the *schedule* (admission, retirement, clocks,
policies) and the *decode discipline* (how many tokens one round
commits, at which tiers) vary independently:

* :class:`GreedyDecode` — one jitted pool decode per round, greedy
  argmax fused in.  Bit-for-bit the historical scheduler behavior.
* :class:`SelfSpeculative` — self-speculative decoding across quality
  tiers.  The paper's accuracy-configurable multiplier gives the pool a
  *free draft model*: the same weights decoded at a cheap tier (larger
  effective splitting point ``t``, deferred carries) propose ``k``
  tokens, then **one** batched ``(B, k+1)`` forward on the verify
  tier's engine scores all proposals together.  Every committed token
  is the *verify* engine's greedy argmax, so the output stream is
  bit-identical to plain decode on the verify engine — speculation
  only changes how many verify-quality tokens one round yields (and
  what it costs on the modeled clock).

Rollback is host-side bookkeeping, not a device operation: both phases
write the *same* physical KV slots (the verify forward overwrites every
draft-quality cache entry before its attention reads them — see
``models.attention``'s per-row ``cache_pos`` path), and a rejected
suffix is "rolled back" simply by not advancing the row's emitted
counter past it, so the next round's writes land on top of the stale
slots.  Key-position masking (queries only attend to cache slots at or
below their own position) keeps the stale suffix invisible meanwhile.

Engines (:class:`TierEngine`, :func:`build_tier_engine`) also live here:
one accuracy tier's jitted (admit, pool-prefill, decode, verify) bundle
over the shared slot pool cache, formerly the scheduler-private
``_TierEngine``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.stats import span
from repro.train.steps import make_decode_step, make_prefill_step

__all__ = [
    "TierEngine",
    "build_tier_engine",
    "make_verify_step",
    "RowView",
    "RoundResult",
    "DecodeStrategy",
    "GreedyDecode",
    "SelfSpeculative",
    "STRATEGIES",
    "get_strategy",
]


def make_verify_step(model):
    """verify(params, caches, tokens (B, S), positions (B, S), starts (B,))
    -> (argmax (B, S) int32, caches).

    One multi-token forward over live caches: row ``i``'s ``S`` tokens
    occupy true positions ``positions[i]`` and write physical cache
    slots ``starts[i] .. starts[i] + S - 1``.  This is the speculative
    verify primitive — ``make_prefill_step`` cannot express it (it
    builds fresh caches and pins the write start to slot 0), and
    ``make_decode_step`` is single-token.
    """
    cfg = model.cfg

    def verify(params, caches, tokens, positions, starts):
        b, s = tokens.shape
        ctx = model.ctx()
        p = jnp.asarray(positions, jnp.int32)
        if cfg.use_mrope:
            p = jnp.broadcast_to(p[None], (3, b, s))
        hidden, new_caches, _ = model.forward(
            params, tokens, p, ctx, caches=caches,
            cache_pos=jnp.asarray(starts, jnp.int32),
        )
        logits = model.lm_head(params, hidden)
        return jnp.argmax(logits, -1).astype(jnp.int32), new_caches

    return verify


@dataclasses.dataclass(frozen=True)
class TierEngine:
    """One accuracy tier's jitted serving steps over the shared slot pool.

    Approximation only changes the forward math — KV cache shapes and
    dtypes are tier-independent — so every engine reads and writes the
    *same* physical pool cache, and switching the serving tier mid-run
    is a dict lookup plus (first visit) a jit compile.  This is the
    serving-layer analogue of reconfiguring an accuracy-configurable
    multiplier's splitting point in place: same hardware (weights +
    cache), different carry-chain cut, near-zero switching cost.
    """

    key: Optional[str]  # engine-cache key (canonical tier, None = pool base)
    name: Optional[str]  # canonical tier name (None = no tier applied)
    admit_step: object  # jitted single-row prefill + scatter + argmax
    prefill_pool: object  # jitted batched pool prefill
    decode: object  # jitted pool decode with fused greedy argmax
    verify: object  # jitted multi-token speculative verify forward
    cost_factor: float  # tier_cycle_factor: virtual clock cost per step


def build_tier_engine(model, capacity: int, *, name, key,
                      scatter_row) -> TierEngine:
    """Jit the (admit, pool-prefill, decode, verify) bundle for one tier.

    ``scatter_row(big, small, row)`` is the admission cache-scatter
    primitive (the scheduler owns it; injected to keep this module free
    of cache-layout knowledge).

    Each step's parts carry a ``jax.named_scope`` (``admit/prefill``,
    ``admit/scatter``, ``admit/argmax``, ``pool_prefill``,
    ``decode/forward``, ``decode/lm_head``, ``decode/argmax``): HLO
    metadata that names the device operations in a profile.
    """
    prefill = make_prefill_step(model, capacity)
    decode = make_decode_step(model)
    verify = make_verify_step(model)

    # Admission, fused to one dispatch: single-row prefill + scatter
    # into the freed slot + greedy first token.
    def admit_step(params, caches, toks, pos, row):
        with jax.named_scope("admit"):
            with jax.named_scope("prefill"):
                row_caches, logits = prefill(
                    params, {"tokens": toks, "positions": pos})
            with jax.named_scope("scatter"):
                caches = scatter_row(caches, row_caches, row)
            with jax.named_scope("argmax"):
                tok0 = jnp.argmax(logits[0, -1], -1).astype(jnp.int32)
        return caches, tok0

    # Initial fill, when the queue covers every slot: one batched
    # prefill *is* the pool cache — no scatter at all.
    def prefill_pool(params, toks, pos):
        with jax.named_scope("pool_prefill"):
            caches, logits = prefill(params, {"tokens": toks, "positions": pos})
            return caches, jnp.argmax(logits[:, -1], -1).astype(jnp.int32)

    # Decode with the greedy argmax fused in (one dispatch per step,
    # and only (B,) token ids cross back to the host).
    def decode_greedy(params, caches, tok, pos, write):
        with jax.named_scope("decode"):
            logits, caches = decode(params, caches, tok, pos, write)
            with jax.named_scope("argmax"):
                return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), caches

    from repro.engine.config import tier_cycle_factor

    return TierEngine(
        key=key,
        name=name,
        admit_step=jax.jit(admit_step, donate_argnums=1),
        prefill_pool=jax.jit(prefill_pool),
        decode=jax.jit(decode_greedy, donate_argnums=1),
        verify=jax.jit(verify, donate_argnums=1),
        cost_factor=tier_cycle_factor(name),
    )


@dataclasses.dataclass(frozen=True)
class RowView:
    """What a strategy may know about one live row.

    A host-side snapshot, not the slot itself: strategies compute
    position/write vectors and token streams from it but never mutate
    scheduler state — commitment (absorb/retire/EOS) stays with the
    scheduler, which is what makes a multi-token round's early stop
    (budget or EOS inside the committed run) safe.
    """

    index: int  # slot index in the pool
    prompt_len: int  # true (unpadded) prompt length
    emitted: int  # tokens emitted so far (>= 1: admission token counted)
    strategy: Optional[str] = None  # per-request tag (None = pool default)


@dataclasses.dataclass(frozen=True)
class RoundResult:
    """One decode round's outcome, as the scheduler consumes it.

    ``tokens[i]`` is the ordered token run committed to row ``i`` —
    every token is final (verify-engine argmax); the scheduler absorbs
    them one at a time so budget/EOS can cut the run short.  ``cost``
    is the round's modeled cost in exact-decode-step units (the
    virtual-clock charge); ``steps`` the number of model forwards.
    """

    tokens: dict  # row index -> list[int]
    caches: object
    steps: int
    cost: float
    proposed: int = 0  # draft tokens proposed this round
    accepted: int = 0  # draft tokens accepted by the verify forward
    per_row: dict = dataclasses.field(default_factory=dict)  # i -> (prop, acc)


class DecodeStrategy:
    """Protocol: one decode round over the live rows of a slot pool.

    ``decode_round(pool, engine, caches, cur_tok, rows, speculate=...)``
    returns a :class:`RoundResult`.  ``pool`` is the scheduler (read
    ``capacity`` / ``params`` / ``prompt_len``, call ``engine_for``);
    ``engine`` the tick's policy-selected :class:`TierEngine`;
    ``cur_tok`` the host-side ``(B, 1)`` array of each row's last
    committed token (strategies must not mutate it); ``rows`` the live
    :class:`RowView` snapshots.
    """

    name = "greedy"
    # whether a round may write tokens it later rejects: the pool must then
    # hold no recurrent state, which a rollback cannot restore
    rolls_back = False

    @property
    def extra_capacity(self) -> int:
        """Extra physical KV slots per row beyond ``prompt_len + max_new``."""
        return 0

    def admission_key(self, policy_key):
        """Engine key admissions (prefill) must run at, given the tick's
        policy-selected key.  Greedy admits at the serving tier; a
        speculative strategy admits at its verify tier so the cache
        prefix is verify-quality from the start."""
        return policy_key

    def warmup(self, pool) -> None:
        """Compile any strategy-specific steps outside the timed region."""

    def decode_round(self, pool, engine, caches, cur_tok, rows,
                     *, speculate: bool = True) -> RoundResult:
        raise NotImplementedError


class GreedyDecode(DecodeStrategy):
    """One pool decode per round: the historical behavior, bit for bit."""

    name = "greedy"

    def decode_round(self, pool, engine, caches, cur_tok, rows,
                     *, speculate: bool = True) -> RoundResult:
        B = cur_tok.shape[0]
        P = pool.prompt_len
        with span("decode.prep"):
            # per-row true position + physical write slot; dead lanes park
            # at the last physical slot with offset 0
            pos = np.full((B,), pool.capacity - 1, np.int32)
            write = np.full((B,), pool.capacity - 1, np.int32)
            for r in rows:
                pos[r.index] = r.prompt_len + r.emitted - 1
                write[r.index] = P + r.emitted - 1
            args = (jnp.asarray(cur_tok), jnp.asarray(pos), jnp.asarray(write))
        with span("decode.dispatch"):
            nxt, caches = engine.decode(pool.params, caches, *args)
        with span("decode.sync"):
            nxt = np.asarray(nxt)
        return RoundResult(
            tokens={r.index: [int(nxt[r.index])] for r in rows},
            caches=caches, steps=1, cost=engine.cost_factor,
        )


class SelfSpeculative(DecodeStrategy):
    """k draft-tier proposal steps + one batched verify forward per round.

    Per live row with last committed token ``c`` at true position ``p0``
    (write slot ``w0``): the draft engine runs ``k`` chained single-token
    decodes producing proposals ``d_1 .. d_k``; the verify engine then
    runs one ``(B, k+1)`` forward over ``(c, d_1 .. d_k)`` at positions
    ``p0 .. p0+k`` writing slots ``w0 .. w0+k`` — overwriting every
    draft-quality cache entry with verify-quality state before its own
    attention reads them.  Position ``j``'s argmax is the verify
    engine's next token after prefix ``.. d_j``; the longest prefix
    where draft and verify agree is accepted and the first disagreement
    position contributes the verify token itself (the "bonus" token), so
    every round commits between 1 and k+1 verify-quality tokens and the
    stream bit-matches plain decode on the verify engine.

    ``verify_tier=None`` verifies at the tick's policy-selected engine
    (the pool tier under ``StaticTier``); a per-pool ``verify_tier``
    pins it.  Rows tagged ``strategy="greedy"`` opt out: a round
    speculates when some live row asked for it, or when no row carries
    a tag at all (pool-level ``--strategy speculative``).
    """

    name = "speculative"
    rolls_back = True

    def __init__(self, k: int = 4, draft_tier: str = "draft",
                 verify_tier: Optional[str] = None):
        if k < 1:
            raise ValueError(f"speculation depth k must be >= 1, got {k}")
        from repro.engine.config import get_tier

        self.k = k
        self.draft_tier = get_tier(draft_tier).name
        self.verify_tier = (
            get_tier(verify_tier).name if verify_tier is not None else None
        )
        # draft == verify is degenerate but legal: accept rate exactly 1.0,
        # modeled gain exactly 1.0 (speculation naturally "off")
        self._greedy = GreedyDecode()

    @property
    def extra_capacity(self) -> int:
        # the verify forward writes up to slot (prompt_len + max_new - 2) + k
        # for a row one token short of budget; k spare slots cover it
        return self.k

    def admission_key(self, policy_key):
        return self.verify_tier if self.verify_tier is not None else policy_key

    def wants_speculation(self, rows: Sequence[RowView]) -> bool:
        tags = [r.strategy for r in rows if r.strategy is not None]
        if not tags:
            return True  # untagged pool: the CLI-level strategy rules
        return any(t == "speculative" for t in tags)

    def warmup(self, pool) -> None:
        """Compile draft decode + verify on throwaway caches."""
        B, cap = pool.batch_size, pool.capacity
        draft = pool.engine_for(self.draft_tier)
        verify = pool.engine_for(self.admission_key(pool.quality))
        caches = pool.model.init_caches(B, cap, pool._cache_dtype)
        zeros = jnp.zeros((B,), jnp.int32)
        _, caches = draft.decode(
            pool.params, caches, jnp.zeros((B, 1), jnp.int32), zeros, zeros)
        ver, caches = verify.verify(
            pool.params, caches, jnp.zeros((B, self.k + 1), jnp.int32),
            jnp.broadcast_to(jnp.arange(self.k + 1, dtype=jnp.int32)[None],
                             (B, self.k + 1)),
            zeros,
        )
        jax.block_until_ready(ver)

    def decode_round(self, pool, engine, caches, cur_tok, rows,
                     *, speculate: bool = True) -> RoundResult:
        verify_eng = (
            pool.engine_for(self.verify_tier)
            if self.verify_tier is not None else engine
        )
        if not speculate or not self.wants_speculation(rows):
            return self._greedy.decode_round(
                pool, verify_eng, caches, cur_tok, rows)
        draft_eng = pool.engine_for(self.draft_tier)
        B = cur_tok.shape[0]
        P, cap, k = pool.prompt_len, pool.capacity, self.k
        live = [r.index for r in rows]
        p0 = np.full((B,), cap - 1, np.int32)  # dead-lane park (offset 0)
        w0 = np.full((B,), cap - 1, np.int32)
        for r in rows:
            p0[r.index] = r.prompt_len + r.emitted - 1
            w0[r.index] = P + r.emitted - 1

        # ---- draft phase: k chained cheap-tier decodes propose d_1..d_k
        props = np.zeros((B, k), np.int32)
        tok = jnp.asarray(cur_tok)  # never mutate the scheduler's array
        for j in range(k):
            pos = np.where(p0 + j < cap, p0 + j, cap - 1).astype(np.int32)
            wrt = np.where(w0 + j < cap, w0 + j, cap - 1).astype(np.int32)
            # live rows never clip (emitted <= max_new - 1 so w0 + k < cap);
            # the where only re-parks dead lanes at the last slot
            with span("decode.dispatch"):
                nxt, caches = draft_eng.decode(
                    pool.params, caches, tok, jnp.asarray(pos), jnp.asarray(wrt))
            with span("decode.sync"):
                props[:, j] = np.asarray(nxt)
            tok = nxt[:, None]

        # ---- verify phase: one (B, k+1) forward on the verify engine,
        # re-writing slots w0..w0+k with verify-quality KV
        vtok = np.concatenate([cur_tok, props], axis=1)  # (B, k+1)
        starts = w0.copy()
        vpos = p0[:, None] + np.arange(k + 1, dtype=np.int32)[None, :]
        live_set = frozenset(live)
        for i in range(B):
            if i not in live_set:
                # dead lane: park the whole window in the spare tail slots
                # (positions arange(k+1): causal, >= 1 visible key, no NaN)
                starts[i] = cap - (k + 1)
                vpos[i] = np.arange(k + 1, dtype=np.int32)
        with span("decode.dispatch"):
            ver, caches = verify_eng.verify(
                pool.params, caches, jnp.asarray(vtok), jnp.asarray(vpos),
                jnp.asarray(starts),
            )
        with span("decode.sync"):
            ver = np.asarray(ver)

        # ---- accept: longest agreeing prefix + the verify bonus token
        tokens: dict = {}
        per_row: dict = {}
        proposed = accepted = 0
        for r in rows:
            i = r.index
            a = 0
            while a < k and props[i, a] == ver[i, a]:
                a += 1
            tokens[i] = [int(t) for t in ver[i, : a + 1]]
            per_row[i] = (k, a)
            proposed += k
            accepted += a
        cost = k * draft_eng.cost_factor + verify_eng.cost_factor
        return RoundResult(
            tokens=tokens, caches=caches, steps=k + 1, cost=cost,
            proposed=proposed, accepted=accepted, per_row=per_row,
        )


STRATEGIES = {
    "greedy": GreedyDecode,
    "speculative": SelfSpeculative,
}


def get_strategy(strategy, **kwargs) -> DecodeStrategy:
    """Resolve a strategy name (or pass an instance through) for the CLIs."""
    if strategy is None:
        strategy = "greedy"
    if isinstance(strategy, DecodeStrategy):
        if kwargs:
            raise ValueError("cannot pass strategy kwargs with an instance")
        return strategy
    try:
        cls = STRATEGIES[strategy]
    except KeyError:
        raise ValueError(
            f"unknown decode strategy {strategy!r}; known: {sorted(STRATEGIES)}"
        ) from None
    return cls(**kwargs)
