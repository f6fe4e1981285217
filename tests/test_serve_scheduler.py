"""Continuous-batching scheduler correctness (repro.serve).

Three properties pin the subsystem down:

* **Parity** — with full-length prompts and uniform budgets (no padding,
  no retirement churn) the continuous scheduler must bit-match the
  static-batch loop: same greedy token streams for the same seed/queue.
* **True-position correctness** — with *mixed* prompt lengths, every
  request's stream must bit-match the request served alone, unpadded
  (batch 1, bucket == its true length).  The static loop fails this by
  construction (all rows share the ``arange`` position ids); the per-row
  position vectors are the fix.
* **Per-row retirement** — a 3-prompt queue on 2 slots must admit the
  third request mid-stream (``admit_step > 0``) without re-prefilling
  the surviving row, and still serve everyone their budgeted tokens.
"""

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.registry import build_model
from repro.serve import (
    ContinuousScheduler,
    Request,
    continuous_serve_loop,
    static_serve_loop,
    synth_requests,
)

PROMPT, GEN = 8, 4


@pytest.fixture(scope="module")
def served():
    cfg = get_config("qwen3-0.6b").reduced()
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def test_parity_with_static_batch_loop(served):
    """No padding, uniform budgets: continuous ≡ static, bit for bit."""
    cfg, model, params = served
    rng = np.random.default_rng(3)
    queue = [
        Request(id=i, tokens=rng.integers(0, cfg.vocab_size, PROMPT).astype(np.int32),
                max_new=GEN)
        for i in range(4)
    ]
    static = static_serve_loop(
        model, params, queue, batch_size=2, prompt_len=PROMPT, gen=GEN, warmup=False
    )
    cont = continuous_serve_loop(
        model, params, queue, batch_size=2, prompt_len=PROMPT, max_new=GEN, warmup=False
    )
    assert static.stats.tokens_out == cont.stats.tokens_out == 4 * GEN
    for r in queue:
        np.testing.assert_array_equal(
            static.outputs[r.id], cont.outputs[r.id],
            err_msg=f"request {r.id}: continuous diverged from the static loop",
        )


def test_padded_rows_decode_at_true_positions(served):
    """Mixed lengths: every stream == the request served alone, unpadded."""
    cfg, model, params = served
    queue = synth_requests(
        6, prompt_len=PROMPT, gen=GEN, vocab_size=cfg.vocab_size, seed=0
    )
    assert len({r.prompt_len for r in queue}) > 1, "workload must mix lengths"
    cont = continuous_serve_loop(
        model, params, queue, batch_size=2, prompt_len=PROMPT, max_new=GEN, warmup=False
    )
    for r in queue:
        alone = static_serve_loop(
            model, params, [r], batch_size=1, prompt_len=r.prompt_len,
            gen=r.max_new, warmup=False,
        )
        np.testing.assert_array_equal(
            alone.outputs[r.id], cont.outputs[r.id],
            err_msg=f"request {r.id} (len {r.prompt_len}): padded decode diverged "
                    f"from the unpadded single-request run",
        )


def test_third_request_admitted_mid_stream(served):
    """3 prompts on 2 slots: the third is admitted once a row retires."""
    cfg, model, params = served
    rng = np.random.default_rng(7)
    queue = [
        Request(id=0, tokens=rng.integers(0, cfg.vocab_size, 6).astype(np.int32), max_new=2),
        Request(id=1, tokens=rng.integers(0, cfg.vocab_size, PROMPT).astype(np.int32), max_new=GEN),
        Request(id=2, tokens=rng.integers(0, cfg.vocab_size, 5).astype(np.int32), max_new=2),
    ]
    cont = continuous_serve_loop(
        model, params, queue, batch_size=2, prompt_len=PROMPT, max_new=GEN, warmup=False
    )
    assert cont.stats.requests == 3
    assert cont.stats_for(0).admit_step == 0
    assert cont.stats_for(1).admit_step == 0
    third = cont.stats_for(2)
    assert third.admit_step > 0, "third request must be admitted mid-stream"
    for r in queue:
        assert len(cont.outputs[r.id]) == r.max_new
        assert cont.stats_for(r.id).finish_reason == "budget"
    # the admission must not have re-prefilled (or perturbed) the survivor:
    alone = static_serve_loop(
        model, params, [queue[1]], batch_size=1, prompt_len=PROMPT, gen=GEN, warmup=False
    )
    np.testing.assert_array_equal(alone.outputs[1], cont.outputs[1])


def test_eos_retires_early(served):
    """A row emitting its eos_id retires before its budget."""
    cfg, model, params = served
    rng = np.random.default_rng(11)
    toks = rng.integers(0, cfg.vocab_size, PROMPT).astype(np.int32)
    # find the first greedy token, then use it as the EOS id
    probe = continuous_serve_loop(
        model, params, [Request(id=0, tokens=toks, max_new=GEN)],
        batch_size=1, prompt_len=PROMPT, max_new=GEN, warmup=False,
    )
    eos = int(probe.outputs[0][0])
    cont = continuous_serve_loop(
        model, params, [Request(id=0, tokens=toks, max_new=GEN, eos_id=eos)],
        batch_size=1, prompt_len=PROMPT, max_new=GEN, warmup=False,
    )
    assert cont.stats_for(0).finish_reason == "eos"
    assert cont.stats_for(0).tokens_out == 1
    assert cont.stats.decode_steps == 0


def test_slot_utilization_and_stats_surface(served):
    cfg, model, params = served
    queue = synth_requests(
        5, prompt_len=PROMPT, gen=GEN, vocab_size=cfg.vocab_size, seed=1
    )
    cont = continuous_serve_loop(
        model, params, queue, batch_size=2, prompt_len=PROMPT, max_new=GEN, warmup=False
    )
    s = cont.stats
    assert s.scheduler == "continuous"
    assert 0.0 < s.slot_utilization <= 1.0
    assert len(s.ttft_s) == len(s.request_latencies_s) == 5
    assert all(t > 0 for t in s.ttft_s)
    assert all(l >= t for l, t in zip(s.request_latencies_s, s.ttft_s))
    assert s.tokens_out == sum(r.max_new for r in queue)
    assert "continuous" in s.summary()


def test_admission_rejects_oversized_requests(served):
    cfg, model, params = served
    sched = ContinuousScheduler(
        model, params, batch_size=1, prompt_len=4, max_new=2
    )
    too_long = Request(id=0, tokens=np.zeros(5, np.int32), max_new=1)
    with pytest.raises(ValueError, match="exceeds bucket"):
        sched.run([too_long], warmup=False)
    too_greedy = Request(id=0, tokens=np.zeros(4, np.int32), max_new=3)
    with pytest.raises(ValueError, match="exceeds slot capacity"):
        sched.run([too_greedy], warmup=False)


def test_recurrent_family_rejects_padded_admission():
    """RG-LRU state integrates left pads (positions cannot mask it), so
    padded admission must raise instead of silently decoding wrong —
    full-length prompts still serve fine.  (SSD layers mask their pads:
    tests/test_falcon_h1.py.)"""
    cfg = get_config("recurrentgemma-2b").reduced()
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    short = Request(id=0, tokens=rng.integers(0, cfg.vocab_size, 4).astype(np.int32),
                    max_new=2)
    with pytest.raises(ValueError, match="recurrent-state"):
        continuous_serve_loop(model, params, [short], batch_size=1,
                              prompt_len=8, max_new=2, warmup=False)
    full = Request(id=1, tokens=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                   max_new=2)
    res = continuous_serve_loop(model, params, [full], batch_size=1,
                                prompt_len=8, max_new=2, warmup=False)
    assert res.stats_for(1).tokens_out == 2


def test_encdec_rejected():
    cfg = get_config("seamless-m4t-large-v2").reduced()
    model = build_model(cfg)
    with pytest.raises(ValueError, match="decoder-only"):
        ContinuousScheduler(model, params=None, batch_size=1, prompt_len=4, max_new=2)


def test_quality_tier_parity_and_stats(served):
    """Per-tier serving: the pool resolves the tier to an engine config
    (controller-selected per-GEMM-class splits) and the continuous
    scheduler still bit-matches the static loop at that tier."""
    cfg, model, params = served
    rng = np.random.default_rng(13)
    queue = [
        Request(id=i, tokens=rng.integers(0, cfg.vocab_size, PROMPT).astype(np.int32),
                max_new=GEN, quality="balanced")
        for i in range(2)
    ]
    static = static_serve_loop(
        model, params, queue, batch_size=2, prompt_len=PROMPT, gen=GEN,
        warmup=False, quality="balanced",
    )
    cont = continuous_serve_loop(
        model, params, queue, batch_size=2, prompt_len=PROMPT, max_new=GEN,
        warmup=False, quality="balanced",
    )
    assert static.stats.quality == cont.stats.quality == "balanced"
    assert "tier balanced" in cont.stats.summary()
    for r in queue:
        np.testing.assert_array_equal(
            static.outputs[r.id], cont.outputs[r.id],
            err_msg=f"request {r.id}: tier-resolved continuous diverged from static",
        )
    # the tier actually changes the computation vs the unconfigured pool
    plain = continuous_serve_loop(
        model, params,
        [Request(id=r.id, tokens=r.tokens, max_new=r.max_new) for r in queue],
        batch_size=2, prompt_len=PROMPT, max_new=GEN, warmup=False,
    )
    assert any(
        not np.array_equal(plain.outputs[r.id], cont.outputs[r.id]) for r in queue
    ), "balanced tier produced bit-identical streams to the exact pool"


def test_quality_tier_mismatch_rejected_at_admission(served):
    cfg, model, params = served
    req_high = Request(id=0, tokens=np.zeros(4, np.int32), max_new=1, quality="high")
    with pytest.raises(ValueError, match="serves 'balanced'"):
        continuous_serve_loop(
            model, params, [req_high], batch_size=1, prompt_len=PROMPT,
            max_new=GEN, warmup=False, quality="balanced",
        )
    with pytest.raises(ValueError, match="without one"):
        continuous_serve_loop(
            model, params, [req_high], batch_size=1, prompt_len=PROMPT,
            max_new=GEN, warmup=False,
        )
    with pytest.raises(ValueError, match="unknown quality tier"):
        ContinuousScheduler(
            model, params, batch_size=1, prompt_len=PROMPT, max_new=GEN,
            quality="no-such-tier",
        )
    # untagged requests ride on any pool; tagged ones match their pool
    ok = continuous_serve_loop(
        model, params,
        [Request(id=1, tokens=np.zeros(4, np.int32), max_new=1),
         Request(id=2, tokens=np.zeros(4, np.int32), max_new=1, quality="high")],
        batch_size=1, prompt_len=PROMPT, max_new=GEN, warmup=False, quality="high",
    )
    assert ok.stats.requests == 2


def test_empty_distribution_summary_renders_na():
    """percentile() returns None on empty input — summary() must say n/a,
    not a misleading 'ttft p50 0ms', when nothing retired."""
    from repro.serve.stats import ServeStats, fmt_ms

    empty = ServeStats(
        requests=0, tokens_out=0, wall_s=0.0, prefill_s=0.0, decode_s=0.0,
        batch_latencies_s=(), devices=1, scheduler="continuous",
    )
    assert "ttft p50 n/a" in empty.summary()
    assert "0ms" not in empty.summary()
    assert fmt_ms((), 50) == "n/a"
    assert fmt_ms((0.1,), 50) == "100ms"
    full = ServeStats(
        requests=1, tokens_out=1, wall_s=1.0, prefill_s=0.0, decode_s=1.0,
        batch_latencies_s=(), devices=1, scheduler="continuous",
        ttft_s=(0.25,),
    )
    assert "ttft p50 250ms" in full.summary()


def test_data_parallel_mesh_helper():
    from repro.distributed.sharding import data_parallel_mesh

    # single device: no mesh, serving runs unsharded
    if jax.device_count() == 1:
        assert data_parallel_mesh(4) is None
    else:
        mesh = data_parallel_mesh(jax.device_count())
        assert mesh is not None and mesh.axis_names == ("data",)


def test_scheduler_under_explicit_mesh(served):
    """A 1-device ('data',) mesh context must not change the streams."""
    cfg, model, params = served
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    queue = synth_requests(
        3, prompt_len=PROMPT, gen=GEN, vocab_size=cfg.vocab_size, seed=5
    )
    plain = continuous_serve_loop(
        model, params, queue, batch_size=1, prompt_len=PROMPT, max_new=GEN, warmup=False
    )
    sharded = continuous_serve_loop(
        model, params, queue, batch_size=1, prompt_len=PROMPT, max_new=GEN,
        mesh=mesh, warmup=False,
    )
    for r in queue:
        np.testing.assert_array_equal(plain.outputs[r.id], sharded.outputs[r.id])
