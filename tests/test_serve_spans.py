"""Host spans and per-token stamps of the continuous scheduler.

``ContinuousScheduler.run`` names its host work with
:func:`repro.serve.stats.span` and reports the per-run totals as
``ServeStats.spans``; ``RequestStats.token_s`` stamps every token on the
run clock.  Checked here on a toy pool in both loops, under the profiler
on the CPU, and in the compiled steps' HLO metadata (``jax.named_scope``).
"""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.registry import build_model
from repro.serve import ContinuousScheduler, synth_requests
from repro.serve.stats import SpanTotals, span

PROMPT, GEN = 8, 4
# the spans directly inside a tick, and each child's parent
TOP = ("policy", "admit", "idle", "decode", "absorb", "pool_prefill")
PARENT = {"admit.dispatch": "admit", "admit.sync": "admit",
          "decode.prep": "decode", "decode.dispatch": "decode", "decode.sync": "decode",
          "pool_prefill.sync": "pool_prefill"}
LOOPS = {
    "closed": {},
    "open-wall": {"clock": "wall"},
    "open-virtual": {"clock": "virtual"},
}


@pytest.fixture(scope="module")
def sched():
    cfg = get_config("qwen3-0.6b").reduced()
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    s = ContinuousScheduler(model, params, batch_size=2, prompt_len=PROMPT, max_new=GEN)
    s.warmup()
    return s


def _queue(sched, n=6):
    return synth_requests(n, prompt_len=PROMPT, gen=GEN,
                          vocab_size=sched.model.cfg.vocab_size, seed=0)


def _serve(sched, loop):
    reqs = _queue(sched)
    kw = dict(LOOPS[loop])
    if loop != "closed":
        # spread wide enough that the wall-clock pool runs dry between
        # arrivals: the idle span is exercised too
        kw["arrivals_s"] = [0.03 * i for i in range(len(reqs))]
    return sched.run(reqs, warmup=False, **kw)


@pytest.fixture(scope="module")
def results(sched):
    return {loop: _serve(sched, loop) for loop in LOOPS}


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_ticks_cover_every_decode_round(results, loop):
    st = results[loop].stats
    assert st.decode_steps > 0
    assert st.spans["tick"].count >= st.decode_steps
    # greedy: one model step, one decode round
    assert st.spans["decode"].count == st.decode_steps
    assert st.spans["decode.sync"].count == st.decode_steps


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_children_fit_inside_their_parents(results, loop):
    sp = results[loop].stats.spans
    assert sum(sp[n].total_s for n in TOP if n in sp) <= sp["tick"].total_s
    for child, parent in PARENT.items():
        if child in sp:
            assert sp[child].count == sp[parent].count
            assert sp[child].total_s <= sp[parent].total_s
            assert sp[child].max_s <= sp[parent].max_s
    wall = results[loop].stats.wall_s
    for s in sp.values():
        assert 0 < s.max_s <= s.total_s
        assert 0 <= s.max_at_s and s.max_at_s + s.max_s <= wall


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_host_time_is_the_ticks_less_the_waits(results, loop):
    st = results[loop].stats
    assert 0 <= st.host_s <= st.spans["tick"].total_s
    waits = ("admit.sync", "pool_prefill.sync", "decode.sync", "idle")
    assert st.host_s == pytest.approx(
        st.spans["tick"].total_s - sum(st.spans[n].total_s for n in waits if n in st.spans))


def test_each_loop_records_its_own_spans(results):
    closed, wall = results["closed"].stats.spans, results["open-wall"].stats.spans
    assert "pool_prefill" in closed and "pool_prefill.sync" in closed
    assert "pool_prefill" not in wall
    assert wall["idle"].count >= 1  # arrivals 30 ms apart outlast a toy request
    assert wall["admit"].count == results["open-wall"].stats.requests


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_token_stamps_are_per_token_and_ordered(results, loop):
    res = results[loop]
    assert res.request_stats
    for rs in res.request_stats:
        assert len(rs.token_s) == rs.tokens_out
        assert all(b >= a for a, b in zip(rs.token_s, rs.token_s[1:]))
        # first token at ttft, last at retirement, both on the run clock
        # shifted to the request's arrival (0 in the closed loop)
        assert rs.token_s[0] - rs.arrival_s == pytest.approx(rs.ttft_s)
        assert rs.token_s[-1] - rs.arrival_s == pytest.approx(rs.latency_s)
        assert rs.token_s[0] >= rs.arrival_s


def test_closed_loop_stamps_lie_inside_the_run(results):
    st = results["closed"].stats
    for rs in results["closed"].request_stats:
        assert 0 < rs.token_s[0] and rs.token_s[-1] <= st.wall_s


def test_speculative_rounds_sync_under_decode(sched):
    from repro.serve.strategy import SelfSpeculative

    spec = ContinuousScheduler(sched.model, sched.params, batch_size=2, prompt_len=PROMPT,
                               max_new=GEN, strategy=SelfSpeculative(k=2))
    res = spec.run(_queue(sched, 4))
    sp = res.stats.spans
    assert sp["decode"].count < res.stats.decode_steps  # k + 1 steps a round
    # k draft reads and one verify read per speculating round, each a sync
    assert sp["decode.sync"].count >= sp["decode"].count
    assert sp["decode.sync"].total_s <= sp["decode"].total_s
    assert res.stats.host_s >= 0


def test_span_outside_a_run_records_nothing():
    totals = SpanTotals()
    with span("free"):
        pass
    with totals.active():
        with span("a"):
            with span("b"):
                pass
        with span("a"):
            pass
    with span("after"):
        pass
    with totals.active():
        with span("a"):
            time.sleep(0.02)  # the longest "a", begun last
    got = totals.totals()
    assert sorted(got) == ["a", "b"]
    assert got["a"].count == 3 and got["b"].count == 1
    assert got["b"].total_s <= got["a"].total_s
    assert got["a"].max_s >= 0.02
    assert got["a"].max_at_s >= got["b"].max_at_s >= 0


def test_static_stats_have_no_spans():
    from repro.serve.stats import ServeStats

    st = ServeStats(requests=0, tokens_out=0, wall_s=1.0, prefill_s=0.0, decode_s=0.0,
                    batch_latencies_s=(), devices=1)
    assert st.spans == {} and st.host_s is None


def test_spans_nest_on_the_profilers_host_clock(sched, tmp_path):
    """Under ``jax.profiler.trace`` every ``serve:decode.sync`` lies inside a
    ``serve:decode``, which lies inside a ``serve:tick``, on one host line."""
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        res = sched.run(_queue(sched), warmup=False)
    files = sorted(tmp_path.rglob("*.xplane.pb"))
    assert files
    data = ProfileData.from_file(str(files[-1]))
    by_line = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name[len("serve:"):], e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if e.name.startswith("serve:")]
                if evs:
                    by_line[(plane.name, line.name)] = evs
    assert len(by_line) == 1  # the scheduler's one thread
    (evs,) = by_line.values()

    def inside(child, parent):
        return [c for c in evs if c[0] == child
                if not any(p[0] == parent and p[1] <= c[1] and c[2] <= p[2] for p in evs)]

    syncs = [e for e in evs if e[0] == "decode.sync"]
    assert len(syncs) == res.stats.decode_steps
    assert inside("decode.sync", "decode") == []
    assert inside("decode", "tick") == []
    assert inside("admit.sync", "admit") == []


# the names each jitted step's HLO metadata must carry; decode reads the
# unrepeated cache, so only admission repeats k/v to the query heads
DECODE_SCOPES = ("decode/forward", "decode/lm_head", "decode/argmax",
                 "attn/cache_update", "attn/scores", "mlp/")
ADMIT_SCOPES = ("admit/prefill", "admit/scatter", "admit/argmax",
                "attn/cache_update", "attn/gqa_repeat", "attn/scores", "mlp/")


def _op_names(compiled) -> str:
    return "\n".join(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def test_compiled_steps_carry_named_scopes(sched):
    eng, B, cfg = sched._base_engine, sched.batch_size, sched.model.cfg
    assert cfg.num_heads > cfg.num_kv_heads  # GQA: the repeat is there
    caches = sched.model.init_caches(B, sched.capacity, jnp.dtype(cfg.dtype))
    z = jnp.zeros((B,), jnp.int32)
    decode = _op_names(eng.decode.lower(
        sched.params, caches, jnp.zeros((B, 1), jnp.int32), z, z).compile())
    for scope in DECODE_SCOPES:
        assert scope in decode, scope
    assert "attn/gqa_repeat" not in decode
    toks = jnp.zeros((1, PROMPT), jnp.int32)
    admit = _op_names(eng.admit_step.lower(
        sched.params, caches, toks, toks, jnp.int32(0)).compile())
    for scope in ADMIT_SCOPES:
        assert scope in admit, scope
    pool = _op_names(eng.prefill_pool.lower(
        sched.params, jnp.zeros((B, PROMPT), jnp.int32),
        jnp.zeros((B, PROMPT), jnp.int32)).compile())
    assert "pool_prefill/forward" in pool


def test_named_scopes_move_no_token(sched):
    """Scopes are metadata: the pool's tokens equal an unscoped forward's
    greedy tokens, step for step."""
    from repro.train.steps import make_decode_step

    B, cfg = sched.batch_size, sched.model.cfg
    caches = sched.model.init_caches(B, sched.capacity, jnp.dtype(cfg.dtype))
    pos = jnp.asarray(np.arange(B, dtype=np.int32))
    tok = jnp.asarray(np.arange(1, B + 1, dtype=np.int32)[:, None])

    def plain(params, caches, tok, pos, write):
        hidden, _, _ = sched.model.forward(params, tok, pos[:, None], sched.model.ctx(),
                                           caches=caches, cache_pos=write)
        return jnp.argmax(sched.model.lm_head(params, hidden)[:, -1], -1)

    want = jax.jit(plain)(sched.params, caches, tok, pos, pos)
    logits, _ = jax.jit(make_decode_step(sched.model))(sched.params, caches, tok, pos, pos)
    got, _ = sched._base_engine.decode(sched.params, caches, tok, pos, pos)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(jnp.argmax(logits[:, -1], -1)), np.asarray(want))
