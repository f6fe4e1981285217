"""A decode step reads the KV pool in place and writes only the new token.

The decode path of ``models.attention`` contracts the grouped query
against the unrepeated bf16 cache, keeps the new token's own key beside
the cache in the softmax, and leaves the write to one scatter after the
layer scan.  These tests hold it to the formulation it replaced — write
the token into the cache row by row, repeat k/v to every query head in
float32, attend over the written cache — kept below as a reference, over
pools whose unwritten slots hold stale values, and check that the donated
pool changes at the written slots only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import attention as attn_mod
from repro.models import layers, transformer
from repro.models.attention import KVCache
from repro.models.registry import build_model
from repro.train.steps import make_decode_step

P, T, STEPS = 8, 24, 3  # prompt bucket, slots a row, decode steps

# per-row (true prompt length, tokens emitted) in a bucket of P; None is a
# dead lane parked at the last slot
LAYOUTS = {
    # rows at different depths, each left-padded to the bucket
    "depths": [(3, 5), (8, 1), (6, 10), (1, 13)],
    # a row just refilled after a longer one retired: its slots past the
    # prompt still hold the old occupant's entries; a retired row parked
    "refill": [(7, 12), (5, 1), (2, 4), None],
}

CASES = {
    # name: (arch, config overrides, layout, scalar cache_pos)
    "g1": ("qwen3-0.6b", dict(num_heads=4, num_kv_heads=4), "depths", False),
    "g2": ("qwen3-0.6b", dict(num_heads=4, num_kv_heads=2), "depths", False),
    "g8": ("qwen3-0.6b", dict(num_heads=16, num_kv_heads=2), "depths", False),
    "g2-refill": ("qwen3-0.6b", dict(num_heads=4, num_kv_heads=2), "refill", False),
    # one scanned (local, global) group and an unrolled local layer
    "window-softcap": ("gemma2-9b", dict(num_heads=4, num_kv_heads=2, local_window=4,
                                         num_layers=3), "refill", False),
    "g2-scalar": ("qwen3-0.6b", dict(num_heads=4, num_kv_heads=2), None, True),
}


def _replaced_attention(params, x, positions, ctx, *, local=False, causal=True,
                        cache=None, cache_pos=None, **_):
    """Decode as it was: a per-row write loop into the cache, then k/v
    repeated from KV to H heads and attended in float32."""
    cfg = ctx.cfg
    b, s, _ = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = layers.dense(x, params["wq"], ctx, "attn").reshape(b, s, h, hd)
    k = layers.dense(x, params["wk"], ctx, "attn").reshape(b, s, kvh, hd)
    v = layers.dense(x, params["wv"], ctx, "attn").reshape(b, s, kvh, hd)
    if cfg.use_qk_norm:
        q = layers.rms_norm(q, params["q_norm_scale"], cfg.norm_eps)
        k = layers.rms_norm(k, params["k_norm_scale"], cfg.norm_eps)
    q = layers.rope(q, positions, cfg.rope_theta)
    k = layers.rope(k, positions, cfg.rope_theta)
    jj = jnp.arange(cache.k.shape[1], dtype=jnp.int32)[None, :] * jnp.ones((b, 1), jnp.int32)
    if getattr(cache_pos, "ndim", 0) >= 1:
        kfull = attn_mod._row_update(cache.k, k.astype(cache.k.dtype), cache_pos)
        vfull = attn_mod._row_update(cache.v, v.astype(cache.v.dtype), cache_pos)
        last = cache_pos + s - 1
        offset = last - positions[:, -1]
        k_pos = jnp.where((jj >= offset[:, None]) & (jj <= last[:, None]),
                          jj - offset[:, None], -1)
    else:
        start = (0, cache_pos, 0, 0)
        kfull = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), start)
        vfull = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), start)
        k_pos = jnp.where(jj <= cache_pos + s - 1, jj, -1)
    g = h // kvh
    out = attn_mod._attend_direct(
        q, jnp.repeat(kfull, g, axis=2), jnp.repeat(vfull, g, axis=2), positions, k_pos,
        causal=causal, window=cfg.local_window if local else None,
        softcap=cfg.attn_logit_softcap, scale=hd**-0.5,
    )
    out = out.reshape(b, s, h * hd).astype(x.dtype)
    return layers.dense(out, params["wo"], ctx, "attn"), KVCache(kfull, vfull)


def _stale_pool(model, rng):
    """A pool whose every slot holds a value: live entries, and in the
    slots a row must not read, stale ones four times as large."""
    caches = model.init_caches(4, T, jnp.bfloat16)
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape) * 4, a.dtype), caches)


def _rows(layout):
    """Per-row (true position, write slot) of the first decode step."""
    pos, write = [], []
    for r in LAYOUTS[layout]:
        if r is None:
            pos.append(T - 1 - STEPS)
            write.append(T - 1 - STEPS)
        else:
            n, emitted = r
            pos.append(n + emitted - 1)
            write.append(P + emitted - 1)
    return np.asarray(pos, np.int32), np.asarray(write, np.int32)


@pytest.mark.parametrize("case", list(CASES))
def test_decode_reads_pool_in_place(case, monkeypatch):
    arch, over, layout, scalar = CASES[case]
    cfg = get_config(arch).reduced(dtype="bfloat16", **over)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    pool = _stale_pool(model, rng)
    toks = rng.integers(0, cfg.vocab_size, (STEPS, 4, 1)).astype(np.int32)
    if scalar:
        pos = write = None
    else:
        pos, write = _rows(layout)

    new = jax.jit(make_decode_step(model), donate_argnums=1)
    with monkeypatch.context() as mp:
        mp.setattr(attn_mod, "attention", _replaced_attention)
        mp.setattr(transformer, "_commit", lambda kind, cache, out, *a: out)
        ref = jax.jit(make_decode_step(model))
        ref_pool = pool
        ref_out = []
        for t in range(STEPS):
            args = (jnp.int32(11 + t),) if scalar else (pos + t, write + t)
            logits, ref_pool = ref(params, ref_pool, toks[t], *args)
            ref_out.append((np.asarray(logits, np.float32), ref_pool))

    new_pool = pool
    for t in range(STEPS):
        args = (jnp.int32(11 + t),) if scalar else (pos + t, write + t)
        before = jax.tree_util.tree_map(np.asarray, new_pool)
        logits, new_pool = new(params, new_pool, toks[t], *args)
        want, want_pool = ref_out[t]
        got = np.asarray(logits, np.float32)
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2,
                                   err_msg=f"{case}: step {t} logits")
        assert (got.argmax(-1) == want.argmax(-1)).all(), f"{case}: step {t} argmax"

        slots = np.full(4, 11 + t) if scalar else write + t
        for leaf, old, ref_leaf in zip(jax.tree_util.tree_leaves(new_pool),
                                       jax.tree_util.tree_leaves(before),
                                       jax.tree_util.tree_leaves(want_pool)):
            # unrolled layers' leaves have no stacked axis
            leaf, old, ref_leaf = (np.asarray(a, np.float32).reshape((-1,) + a.shape[-4:])
                                   for a in (leaf, old, ref_leaf))
            written = np.zeros(leaf.shape[:3], bool)  # (layers, rows, slots)
            written[:, np.arange(4), slots] = True
            np.testing.assert_array_equal(leaf[~written], old[~written],
                                          err_msg=f"{case}: step {t} wrote outside")
            np.testing.assert_allclose(leaf[written], ref_leaf[written],
                                       rtol=2e-2, atol=2e-2)
            assert not np.array_equal(leaf[written], old[written])
        # the first layer's new k/v are bit-equal; deeper layers carry the
        # rounding of the attention output above them
        for got_kv, want_kv in zip(new_pool["scan"]["sub0"], want_pool["scan"]["sub0"]):
            np.testing.assert_array_equal(np.asarray(got_kv[0, np.arange(4), slots]),
                                          np.asarray(want_kv[0, np.arange(4), slots]))
