"""Prefill + decode (KV / recurrent caches) must reproduce the
teacher-forced full forward — the strongest cache-correctness check."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.registry import build_model
from repro.train.steps import make_decode_step, make_prefill_step

# covers: GQA global, local+softcap+postnorm, MQA+RG-LRU hybrid, SSD,
# MoE, M-RoPE, enc-dec cross-attention, parallel attention+SSD (muP)
ARCHS = [
    "qwen3-0.6b", "gemma2-9b", "recurrentgemma-2b", "mamba2-130m",
    "granite-moe-1b-a400m", "qwen2-vl-7b", "seamless-m4t-large-v2", "falcon-h1-34b",
]
B, PROMPT, GEN = 2, 8, 6


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_full_forward(arch):
    cfg = get_config(arch).reduced()
    if cfg.num_experts:
        # capacity-based MoE dropping is batch-dependent by design; lift the
        # capacity so prefill-vs-full-forward parity is well-defined
        cfg = cfg.reduced(capacity_factor=8.0)
    m = build_model(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    total = PROMPT + GEN
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, total)), jnp.int32)

    # ---- reference: full forward over the whole sequence
    ctx = m.ctx()
    pos = jnp.arange(total, dtype=jnp.int32)[None].repeat(B, 0)
    if cfg.use_mrope:
        pos = jnp.broadcast_to(pos[None], (3, B, total))
    kw = {}
    src = None
    if cfg.is_encdec:
        src = jnp.asarray(rng.standard_normal((B, PROMPT, cfg.d_model)), jnp.float32)
        kw["src_embeds"] = src
        kw["src_pos"] = jnp.arange(PROMPT, dtype=jnp.int32)[None].repeat(B, 0)
    hidden_full, _, _ = m.forward(params, toks, pos, ctx, **kw)
    logits_full = np.asarray(m.lm_head(params, hidden_full), np.float32)

    # ---- prefill PROMPT tokens, then decode the rest teacher-forced
    batch = {"tokens": toks[:, :PROMPT]}
    if cfg.is_encdec:
        batch["src_embeds"] = src
        batch["src_pos"] = kw["src_pos"]
    prefill = make_prefill_step(m, total, mem_len=PROMPT if cfg.is_encdec else 0)
    decode = make_decode_step(m)
    caches, logits_p = prefill(params, batch)
    np.testing.assert_allclose(
        np.asarray(logits_p[:, -1], np.float32), logits_full[:, PROMPT - 1],
        rtol=2e-2, atol=2e-2,
    )
    for g in range(GEN):
        tok = toks[:, PROMPT + g][:, None]
        logits_d, caches = decode(params, caches, tok, jnp.int32(PROMPT + g))
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0], np.float32), logits_full[:, PROMPT + g],
            rtol=2e-2, atol=2e-2,
            err_msg=f"{arch}: decode step {g} diverged from full forward",
        )
