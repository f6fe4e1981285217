"""Falcon-H1's parallel Mamba-2 + attention blocks in the program, at a toy
size on the CPU:

* prefill, then decode through the cache, agree in logits with the
  benchmark's plain float32 reference (``perfbench/families/falcon_h1.py``,
  a sequential recurrence where the program runs the chunked scan);
* a left-padded prompt leaves the conv and SSM state and the logits of the
  unpadded prompt, through batch-1 admission and through the pool prefill;
* a hybrid row served by the continuous scheduler among mixed-length
  batch-mates gives the tokens it gives alone, unpadded;
* a strategy that rolls back tokens refuses a pool with recurrent state;
* with one group and no gated norm the SSD mixer reads as before groups,
  norms and multipliers were added.
"""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import ssd, transformer
from repro.models.layers import Ctx
from repro.models.registry import build_model
from repro.serve import ContinuousScheduler, Request, continuous_serve_loop, static_serve_loop
from repro.serve.scheduler import _scatter_row
from repro.serve.strategy import build_tier_engine
from repro.train.steps import make_decode_step, make_prefill_step

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tests" / "perfbench"))

from perfbench import spec  # noqa: E402
from test_perfbench_falcon_h1 import reference_logits  # noqa: E402

SEED = 2 ** 31 + 5
# the registry config's reduced() sizes, as a configuration file states them
_TOY = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=128, vocab_size=256,
            mamba_d_ssm=128, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
            mamba_chunk_size=8)


@pytest.fixture(scope="module")
def toy():
    """(reduced float32 model, its weights from the family, family module,
    configuration dict)."""
    cfg = json.loads((REPO / "perfbench/configs/falcon-h1-34b-8l.json").read_text())
    cfg.update(_TOY, name="toy")
    prog = get_config("falcon-h1-34b").reduced()
    cfg["program"] = {"registry": "falcon-h1-34b",
                      "overrides": {"num_layers": 2, **{
                          f: getattr(prog, f) for f in (
                              "d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
                              "vocab_size", "d_inner", "ssm_heads", "ssm_head_dim",
                              "ssm_state", "ssm_chunk")}}}
    family = spec.Spec(REPO).family(cfg)
    bf16 = build_model(family.program_config(cfg))
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16
                                    else a, family.program_params(cfg, bf16, SEED))
    model = build_model(dataclasses.replace(bf16.cfg, dtype="float32"))
    return model, params, family, cfg


def test_prefill_then_decode_match_the_reference(toy):
    model, params, family, cfg = toy
    prompt, gen = 13, 6
    tokens = np.random.default_rng(1).integers(0, cfg["vocab_size"], (2, prompt + gen))
    ref = reference_logits(family, cfg, SEED, tokens)
    caches, logits = make_prefill_step(model, prompt + gen)(
        params, {"tokens": jnp.asarray(tokens[:, :prompt], jnp.int32)})
    got = [np.asarray(logits[:, -1])]
    decode = make_decode_step(model)
    for g in range(gen - 1):
        logits, caches = decode(params, caches, jnp.asarray(tokens[:, prompt + g, None], jnp.int32),
                                jnp.int32(prompt + g))
        got.append(np.asarray(logits[:, 0]))
    got = np.stack(got, axis=1)
    want = ref[:, prompt - 1:prompt + gen - 1]
    # Both sides are float32 with the same weights; they differ in the order
    # of summation only (chunked scan against the sequential recurrence, an
    # online cache against full attention).  The logits carry
    # lm_head_multiplier (1/128), so they are ~1e-2: a relative error of
    # 1e-4 of their scale is float32 rounding, and a wrong equation
    # (one missing multiplier, group map or norm order) moves them by more
    # than their own scale.
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4 * scale)
    assert np.array_equal(got.argmax(-1), want.argmax(-1))


def _ssd_leaves(caches):
    """(conv, state) of every layer, stacked: scan leaves (L, B, ...)."""
    sub = caches["scan"]["sub0"]
    return sub.ssd.conv, sub.ssd.state


def test_left_pads_leave_the_unpadded_state(toy):
    model, params, family, cfg = toy
    assert any(np.abs(np.asarray(params["scan"]["sub0"]["ssd"]["conv_b"])).max(-1) > 0)
    bucket, capacity = 16, 24
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32) for n in (5, 11, 16)]

    def padded(p):
        toks = np.zeros(bucket, np.int32)
        toks[bucket - len(p):] = p
        return toks, np.arange(bucket, dtype=np.int32) - (bucket - len(p))

    prefill = make_prefill_step(model, capacity)
    alone = []
    for p in prompts:
        caches, logits = prefill(params, {"tokens": jnp.asarray(p[None])})
        alone.append((_ssd_leaves(caches), np.asarray(logits[0, -1])))
    # float32 on both sides; the padded scan sums in other chunks (8 tokens
    # each, from the bucket's start), which moves the last bits only
    tol = dict(rtol=2e-5, atol=2e-6)

    # the padded prefill itself, as admission and the pool run it
    toks, pos = zip(*(padded(p) for p in prompts))
    caches, logits = prefill(params, {"tokens": jnp.asarray(np.stack(toks)),
                                      "positions": jnp.asarray(np.stack(pos))})
    for i, ((conv, state), last) in enumerate(alone):
        np.testing.assert_allclose(np.asarray(logits[i, -1]), last, **tol)
        got_conv, got_state = _ssd_leaves(caches)
        np.testing.assert_allclose(np.asarray(got_conv[:, i]), np.asarray(conv[:, 0]), **tol)
        np.testing.assert_allclose(np.asarray(got_state[:, i]), np.asarray(state[:, 0]), **tol)

    # batch-1 admission into a pool row, and the batched pool prefill
    engine = build_tier_engine(model, capacity, name=None, key=None, scatter_row=_scatter_row)
    pool = model.init_caches(3, capacity, jnp.float32)
    for row, (t, p) in enumerate(zip(toks, pos)):
        pool, _ = engine.admit_step(params, pool, jnp.asarray(t[None]), jnp.asarray(p[None]),
                                    jnp.int32(row))
    filled, _ = engine.prefill_pool(params, jnp.asarray(np.stack(toks)),
                                    jnp.asarray(np.stack(pos)))
    for cache in (pool, filled):
        conv_all, state_all = _ssd_leaves(cache)
        for i, ((conv, state), _) in enumerate(alone):
            np.testing.assert_allclose(np.asarray(conv_all[:, i]), np.asarray(conv[:, 0]), **tol)
            np.testing.assert_allclose(np.asarray(state_all[:, i]), np.asarray(state[:, 0]),
                                       **tol)
    assert model.state_bytes(pool) == sum(a.size * 4 for a in _ssd_leaves(pool)) > 0


def test_hybrid_rows_serve_alone_whatever_their_batch_mates(toy):
    model, params, family, cfg = toy
    # the multipliers at 1, so that the mixers weigh in the logits as much
    # as the embedding does: a pad folded into a row's state then moves its
    # tokens (at the published multipliers, 2 toy layers barely move them)
    unit = dict(embed_multiplier=1.0, lm_head_multiplier=1.0, key_multiplier=1.0,
                attn_out_multiplier=1.0, ssm_in_multiplier=1.0, ssm_out_multiplier=1.0,
                ssm_multipliers=(1.0,) * 5, mlp_multipliers=(1.0, 1.0))
    model = build_model(dataclasses.replace(model.cfg, **unit))
    bucket, gen = 16, 5
    rng = np.random.default_rng(3)
    queue = [Request(id=i, tokens=rng.integers(0, cfg["vocab_size"], n).astype(np.int32),
                     max_new=gen)
             for i, n in enumerate((4, 16, 9, 12, 6, 15, 7))]
    cont = continuous_serve_loop(model, params, queue, batch_size=3, prompt_len=bucket,
                                 max_new=gen, warmup=False)
    assert cont.stats.state_bytes > 0
    assert cont.accounting.admission_seats > 0  # rows admitted beside live ones
    for r in queue:
        alone = static_serve_loop(model, params, [r], batch_size=1, prompt_len=r.prompt_len,
                                  gen=gen, warmup=False)
        np.testing.assert_array_equal(
            alone.outputs[r.id], cont.outputs[r.id],
            err_msg=f"request {r.id} (len {r.prompt_len}) diverged from its unpadded run")


@pytest.mark.parametrize("arch", ["falcon-h1-34b", "mamba2-130m", "recurrentgemma-2b"])
def test_speculation_refuses_recurrent_state(arch):
    model = build_model(get_config(arch).reduced())
    with pytest.raises(ValueError, match="rolls back rejected tokens.*recurrent state"):
        ContinuousScheduler(model, None, batch_size=2, prompt_len=8, max_new=4,
                            strategy="speculative")
    assert ContinuousScheduler(model, None, batch_size=2, prompt_len=8,
                               max_new=4).strategy.name == "greedy"


def test_single_group_ssd_reads_as_before():
    """mamba2-130m's reduced mixer (one group, no gated norm, no
    multipliers), a nonzero conv bias: prefill of 12 tokens (a chunk and a
    half) then two decode steps.  The numbers were read from the mixer
    before groups, the gated norm, the multipliers and pad masking were
    added; they agree to float32 rounding."""
    cfg = get_config("mamba2-130m").reduced()
    assert cfg.ssm_groups == 1 and not cfg.ssm_norm and set(cfg.ssm_multipliers) == {1.0}
    p = ssd.init_ssd(jax.random.key(3), cfg, jnp.float32)
    p["conv_b"] = jax.random.normal(jax.random.key(4), p["conv_b"].shape) * 0.1
    ctx = Ctx(cfg=cfg)
    out, cache = ssd.ssd_block(p, jax.random.normal(jax.random.key(5), (2, 12, cfg.d_model)),
                               ctx, cache=ssd.init_ssd_cache(cfg, 2, jnp.float32),
                               positions=jnp.broadcast_to(jnp.arange(12), (2, 12)))
    outs = [out]
    for t in range(2):
        o, cache = ssd.ssd_block(p, jax.random.normal(jax.random.key(10 + t), (2, 1, cfg.d_model)),
                                 ctx, cache=cache)
        outs.append(o)
    full = np.concatenate([np.asarray(o) for o in outs], axis=1)
    np.testing.assert_allclose([full.sum(), np.abs(full).sum()],
                               [-4.073216438293457, 98.18679809570312], rtol=1e-5)
    np.testing.assert_allclose(
        full[1, [0, 5, 11, 12, 13], 7],
        [0.12113246321678162, -0.10565469413995743, 0.06583963334560394,
         0.006731768604367971, -0.10421363264322281], rtol=1e-5, atol=1e-7)
    state = np.asarray(cache.state)
    np.testing.assert_allclose([state.sum(), np.abs(state).sum()],
                               [0.364433228969574, 2.4827024936676025], rtol=1e-5)
    assert transformer.state_bytes({"scan": {"sub0": cache}}) == (
        cache.conv.size + cache.state.size) * 4


def test_training_loss_scales_the_logits_as_the_head_does():
    """The vocab-chunked training loss sees lm_head_multiplier as
    ``lm_head`` applies it: it equals the cross-entropy of the full logits."""
    from repro.train.losses import cross_entropy_dense
    from repro.train.steps import loss_fn

    model = build_model(get_config("falcon-h1-34b").reduced())
    assert model.cfg.lm_head_multiplier != 1.0
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {k: jnp.asarray(rng.integers(0, model.cfg.vocab_size, (2, 16)), jnp.int32)
             for k in ("tokens", "labels")}
    loss, _ = loss_fn(params, batch, jax.random.PRNGKey(1), model)
    hidden, _, _ = model.forward(params, batch["tokens"],
                                 jnp.broadcast_to(jnp.arange(16), (2, 16)), model.ctx())
    full = cross_entropy_dense(model.lm_head(params, hidden), batch["labels"])
    np.testing.assert_allclose(float(loss), float(full), rtol=1e-5)
