"""A decode step carries recurrent state through the layer scan in place.

In a decode step the scanned layers' recurrent state (SSD conv and SSM
state, RG-LRU conv and hidden state) rides in the layer scan's carry: each
layer reads its slice at the scan index and writes its new state back,
while the KV pool is still read as ``xs`` and written by one scatter after
the scan.  These tests hold it to the formulation it replaced — every
cache leaf in as ``xs``, the whole new state out as stacked ``ys``, the
SSD step's outer product as an einsum — kept below as a reference, over
several rounds of a pool with rows at different depths and a dead lane,
and check that logits and every pool leaf are bit-identical.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import ssd, transformer
from repro.models.registry import build_model
from repro.train.steps import make_decode_step

P, T, STEPS = 8, 24, 4  # prompt bucket, slots a row, decode rounds
# per-row (true prompt length, tokens emitted) in a bucket of P; None is a
# dead lane parked near the last slot
ROWS = [(3, 5), (8, 1), (6, 10), None]

CASES = {
    # name: (arch, config overrides); the SSD cases keep the published
    # head_dim < state ratio (falcon-h1: 128 < 256)
    "attn_ssd": ("falcon-h1-34b", dict(num_layers=3, ssm_state=32, remat="full")),
    "ssd": ("mamba2-130m", dict(num_layers=2, ssm_state=32)),
    # two scanned (rglru, rglru, attn_local) groups and an unrolled rglru layer
    "rglru": ("recurrentgemma-2b", dict(num_layers=7)),
}


def _einsum_ssd_step(xh, dt, a, b_in, c_in, state):
    """The SSD decode step as it was, its outer product an einsum."""
    bsz, h, p = xh.shape
    g, n = b_in.shape[-2:]
    k = h // g
    dtg = dt.reshape(bsz, g, k)
    da = jnp.exp(dtg * a.reshape(g, k))
    dbx = jnp.einsum("bgk,bgn,bgkp->bgkpn", dtg, b_in, xh.reshape(bsz, g, k, p))
    state = da[..., None, None] * state.reshape(bsz, g, k, p, n) + dbx
    y = jnp.einsum("bgn,bgkpn->bgkp", c_in, state)
    return y.reshape(bsz, h, p), state.reshape(bsz, h, p, n)


def _positions():
    """Per-row (true position, write slot) of the first decode round."""
    pos, write = [], []
    for r in ROWS:
        if r is None:
            pos.append(T - 1 - STEPS)
            write.append(T - 1 - STEPS)
        else:
            n, emitted = r
            pos.append(n + emitted - 1)
            write.append(P + emitted - 1)
    return np.asarray(pos, np.int32), np.asarray(write, np.int32)


@pytest.mark.parametrize("case", list(CASES))
def test_decode_state_carried_in_place(case, monkeypatch):
    arch, over = CASES[case]
    cfg = get_config(arch).reduced(dtype="bfloat16", **over)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    pool = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        model.init_caches(len(ROWS), T, jnp.bfloat16))
    assert model.state_bytes(pool) > 0
    toks = rng.integers(0, cfg.vocab_size, (STEPS, len(ROWS), 1)).astype(np.int32)
    pos, write = _positions()

    with monkeypatch.context() as mp:
        mp.setattr(transformer, "_split_recurrent", lambda caches: (caches, None))
        mp.setattr(ssd, "_ssd_step", _einsum_ssd_step)
        ref = jax.jit(make_decode_step(model))
        ref_pool, want = pool, []
        for t in range(STEPS):
            logits, ref_pool = ref(params, ref_pool, toks[t], pos + t, write + t)
            want.append((np.asarray(logits, np.float32),
                         jax.tree_util.tree_map(np.asarray, ref_pool)))

    new = jax.jit(make_decode_step(model), donate_argnums=1)
    new_pool = pool
    for t in range(STEPS):
        logits, new_pool = new(params, new_pool, toks[t], pos + t, write + t)
        want_logits, want_pool = want[t]
        np.testing.assert_array_equal(np.asarray(logits, np.float32), want_logits,
                                      err_msg=f"{case}: round {t} logits")
        got = jax.tree_util.tree_leaves_with_path(new_pool)
        assert jax.tree_util.tree_structure(new_pool) == jax.tree_util.tree_structure(
            want_pool)
        for (path, leaf), ref_leaf in zip(got, jax.tree_util.tree_leaves(want_pool)):
            assert leaf.dtype == ref_leaf.dtype, jax.tree_util.keystr(path)
            np.testing.assert_array_equal(
                np.asarray(leaf), ref_leaf,
                err_msg=f"{case}: round {t} {jax.tree_util.keystr(path)}")
