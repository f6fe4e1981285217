"""Pallas flash-attention kernel vs. the models.attention oracle —
forward and gradients, sweeping causal/window/softcap/GQA (interpret)."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.models.attention import _attend_direct


def _inputs(b=2, s=64, t=64, h=4, kv=2, hd=32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, s, h, hd)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, kv, hd)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, kv, hd)) * 0.5, jnp.float32)
    q_pos = jnp.arange(s, dtype=jnp.int32)[None].repeat(b, 0) + (t - s)
    k_pos = jnp.arange(t, dtype=jnp.int32)[None].repeat(b, 0)
    return q, k, v, q_pos, k_pos


def _oracle(q, k, v, q_pos, k_pos, causal, window, softcap, scale):
    g = q.shape[2] // k.shape[2]
    kk = jnp.repeat(k, g, axis=2)
    vv = jnp.repeat(v, g, axis=2)
    return _attend_direct(q, kk, vv, q_pos, k_pos, causal=causal, window=window,
                          softcap=softcap, scale=scale)


CASES = [
    dict(causal=True, window=None, softcap=None),
    dict(causal=True, window=16, softcap=None),
    dict(causal=True, window=None, softcap=20.0),
    dict(causal=False, window=None, softcap=None),
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 16)])
def test_forward_matches_oracle(case, bq, bk):
    q, k, v, qp, kp = _inputs()
    scale = q.shape[-1] ** -0.5
    got = flash_attention(q, k, v, qp, kp, case["causal"], case["window"],
                          case["softcap"], scale, bq, bk, True)
    want = _oracle(q, k, v, qp, kp, scale=scale, **case)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("h,kv", [(4, 4), (4, 1), (8, 2)])
def test_gqa_head_mapping(h, kv):
    q, k, v, qp, kp = _inputs(h=h, kv=kv, seed=h * 10 + kv)
    scale = q.shape[-1] ** -0.5
    got = flash_attention(q, k, v, qp, kp, True, None, None, scale, 32, 32, True)
    want = _oracle(q, k, v, qp, kp, causal=True, window=None, softcap=None, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_masked_cache_slots():
    """k_pos = -1 marks unwritten cache slots; they must not attend."""
    q, k, v, qp, kp = _inputs(s=16, t=64)
    kp = jnp.where(kp < 40, kp, -1)  # only 40 valid slots
    qp = jnp.minimum(qp, 39)
    scale = q.shape[-1] ** -0.5
    got = flash_attention(q, k, v, qp, kp, True, None, None, scale, 16, 32, True)
    want = _oracle(q, k, v, qp, kp, causal=True, window=None, softcap=None, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", CASES[:3])
def test_gradients_match_oracle(case):
    q, k, v, qp, kp = _inputs(b=1, s=32, t=32, h=2, kv=1, hd=16)
    scale = q.shape[-1] ** -0.5

    def loss_kernel(q, k, v):
        o = flash_attention(q, k, v, qp, kp, case["causal"], case["window"],
                            case["softcap"], scale, 16, 16, True)
        return jnp.sum(o * jnp.cos(o))

    def loss_oracle(q, k, v):
        o = _oracle(q, k, v, qp, kp, scale=scale, **case)
        return jnp.sum(o * jnp.cos(o))

    g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_oracle, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_bf16_inputs():
    q, k, v, qp, kp = _inputs()
    scale = q.shape[-1] ** -0.5
    got = flash_attention(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                          v.astype(jnp.bfloat16), qp, kp, True, None, None,
                          scale, 32, 32, True)
    want = _oracle(q, k, v, qp, kp, causal=True, window=None, softcap=None, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("kernel", ["flash_attention", "flash_decode"])
def test_omitted_interpret_follows_engine_policy(kernel, monkeypatch):
    """With ``interpret`` omitted the kernels resolve it through
    ``engine.policy``: interpret mode when forced, native lowering (which
    the CPU refuses) when native is forced — never a silent default."""
    from repro.kernels.flash_attention import flash_decode

    q, k, v, qp, kp = _inputs(s=1 if kernel == "flash_decode" else 32, t=32)
    scale = q.shape[-1] ** -0.5
    if kernel == "flash_decode":
        def run():
            return flash_decode(q[:, 0], k, v, qp[:, 0], kp, scale=scale, bk=16)
        want = _oracle(q, k, v, qp, kp, causal=True, window=None, softcap=None,
                       scale=scale)[:, 0]
    else:
        def run():
            return flash_attention(q, k, v, qp, kp, True, None, None, scale, 16, 16)
        want = _oracle(q, k, v, qp, kp, causal=True, window=None, softcap=None, scale=scale)
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    np.testing.assert_allclose(np.asarray(run()), np.asarray(want), rtol=2e-5, atol=2e-5)
    if jax.default_backend() == "cpu":
        monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")
        with pytest.raises(Exception):
            run()
