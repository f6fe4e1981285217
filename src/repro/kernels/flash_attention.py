"""Pallas TPU flash attention (forward + backward, custom_vjp).

The XLA-lowered blockwise attention keeps every (bq × bk) score block in
HBM (logits, probs, selects) and hoists the position masks out of the
layer scan as multi-GB loop carries (EXPERIMENTS.md §Perf iteration 3).
This kernel keeps the online-softmax state in VMEM: per (batch, head,
q-block) the running (m, l, acc) live in the revisited output block, so
score blocks never round-trip to HBM and masks are recomputed from
positions in-register — the flash-attention transformation, tiled for
the MXU (block sizes multiples of 128).

Features: causal masking, sliding window, logit softcap (Gemma2), GQA
via an index-mapped KV head (k/v are *not* repeated in HBM — each query
head's BlockSpec points at its KV group), explicit positions (cache
slots with pos < 0 are masked).

Backward follows FlashAttention-2: forward additionally writes
L = m + log(l); backward recomputes probabilities blockwise with one
kernel for dq (grid over q blocks) and one for dk/dv (grid over k
blocks, accumulating across the GQA group).

Validated in interpret mode against the pure-jnp oracle in
``tests/test_flash_kernel.py``; native lowering targets TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.engine.policy import resolve_interpret

__all__ = ["flash_attention", "flash_decode"]

NEG_INF = -2.3819763e38
DEFAULT_BQ = 512
DEFAULT_BK = 512


def _block_mask(qp, kp, causal, window):
    m = kp[None, :] >= 0
    if causal:
        m &= qp[:, None] >= kp[None, :]
    if window is not None:
        m &= qp[:, None] - kp[None, :] < window
    return m


def _dot(a, b, trans_b=False):
    dims = (((1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# ------------------------------------------------------------------ forward
def _fwd_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, o_ref, ml_ref,
                *, causal, window, softcap, scale, nk):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        ml_ref[0, 0, 0, :] = jnp.full((ml_ref.shape[-1],), NEG_INF, jnp.float32)  # m
        ml_ref[0, 1, 0, :] = jnp.zeros((ml_ref.shape[-1],), jnp.float32)  # l

    q = q_ref[0, :, 0, :].astype(jnp.float32)
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    s = _dot(q, k, trans_b=True) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    allow = _block_mask(qp_ref[0, :], kp_ref[0, :], causal, window)
    s = jnp.where(allow, s, NEG_INF)

    m_prev = ml_ref[0, 0, 0, :]
    l_prev = ml_ref[0, 1, 0, :]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    ml_ref[0, 1, 0, :] = l_prev * corr + p.sum(axis=-1)
    ml_ref[0, 0, 0, :] = m_new
    o_ref[0, :, 0, :] = o_ref[0, :, 0, :] * corr[:, None] + _dot(p, v)

    @pl.when(ki == nk - 1)
    def _():
        l = jnp.maximum(ml_ref[0, 1, 0, :], 1e-30)
        o_ref[0, :, 0, :] = o_ref[0, :, 0, :] / l[:, None]
        # final L = m + log l (overwrites the m slot; l slot becomes garbage)
        ml_ref[0, 0, 0, :] = ml_ref[0, 0, 0, :] + jnp.log(l)


def _fwd(q, k, v, q_pos, k_pos, causal, window, softcap, scale, bq, bk, interpret):
    b, s, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    bq, bk = min(bq, s), min(bk, t)
    nq, nk = pl.cdiv(s, bq), pl.cdiv(t, bk)

    o, ml = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, window=window,
                          softcap=softcap, scale=scale, nk=nk),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq), lambda b_, h_, qi, ki: (b_, qi)),
            pl.BlockSpec((1, bk), lambda b_, h_, qi, ki: (b_, ki)),
            pl.BlockSpec((1, bq, 1, hd), lambda b_, h_, qi, ki: (b_, qi, h_, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b_, h_, qi, ki: (b_, ki, h_ // g, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b_, h_, qi, ki: (b_, ki, h_ // g, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, 1, hd), lambda b_, h_, qi, ki: (b_, qi, h_, 0)),
            pl.BlockSpec((1, 2, 1, bq), lambda b_, h_, qi, ki: (b_, 0, h_, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, 2, h, s), jnp.float32),
        ],
        interpret=interpret,
    )(q_pos, k_pos, q, k, v)
    lse = ml[:, 0]  # (B, H, S)
    return o, lse


# ----------------------------------------------------------------- backward
def _dq_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
               dq_ref, *, causal, window, softcap, scale, nk):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    q = q_ref[0, :, 0, :].astype(jnp.float32)
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    do = do_ref[0, :, 0, :].astype(jnp.float32)
    lse = lse_ref[0, 0, 0, :]
    dd = dd_ref[0, 0, 0, :]

    raw = _dot(q, k, trans_b=True) * scale
    if softcap:
        tanh_term = jnp.tanh(raw / softcap)
        s = tanh_term * softcap
    else:
        s = raw
    allow = _block_mask(qp_ref[0, :], kp_ref[0, :], causal, window)
    s = jnp.where(allow, s, NEG_INF)
    p = jnp.exp(s - lse[:, None])  # (bq, bk)
    dp = _dot(do, v, trans_b=True)
    ds = p * (dp - dd[:, None])
    if softcap:
        ds = ds * (1.0 - tanh_term * tanh_term)
    ds = jnp.where(allow, ds, 0.0)
    dq_ref[0, :, 0, :] += _dot(ds, k) * scale


def _dkv_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                dk_ref, dv_ref, *, causal, window, softcap, scale, g, nq):
    gi = pl.program_id(3)
    qi = pl.program_id(4)

    @pl.when((gi == 0) & (qi == 0))
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    q = q_ref[0, :, 0, :].astype(jnp.float32)
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    do = do_ref[0, :, 0, :].astype(jnp.float32)
    lse = lse_ref[0, 0, 0, :]
    dd = dd_ref[0, 0, 0, :]

    raw = _dot(q, k, trans_b=True) * scale  # (bq, bk)
    if softcap:
        tanh_term = jnp.tanh(raw / softcap)
        s = tanh_term * softcap
    else:
        s = raw
    allow = _block_mask(qp_ref[0, :], kp_ref[0, :], causal, window)
    s = jnp.where(allow, s, NEG_INF)
    p = jnp.exp(s - lse[:, None])
    dv_ref[0, :, 0, :] += _dot(p.T, do)
    dp = _dot(do, v, trans_b=True)
    ds = p * (dp - dd[:, None])
    if softcap:
        ds = ds * (1.0 - tanh_term * tanh_term)
    ds = jnp.where(allow, ds, 0.0)
    dk_ref[0, :, 0, :] += _dot(ds.T, q) * scale


def _bwd(causal, window, softcap, scale, bq, bk, interpret, res, do):
    q, k, v, q_pos, k_pos, o, lse = res
    b, s, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    bq_, bk_ = min(bq, s), min(bk, t)
    nq, nk = pl.cdiv(s, bq_), pl.cdiv(t, bk_)
    do = do.astype(jnp.float32)
    dd = jnp.einsum("bshd,bshd->bhs", do, o.astype(jnp.float32))  # (B,H,S)
    lse4 = lse[:, None]  # (B,1,H,S) -> blockspec (1,1,1,bq)
    dd4 = dd[:, None]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, window=window,
                          softcap=softcap, scale=scale, nk=nk),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq_), lambda b_, h_, qi, ki: (b_, qi)),
            pl.BlockSpec((1, bk_), lambda b_, h_, qi, ki: (b_, ki)),
            pl.BlockSpec((1, bq_, 1, hd), lambda b_, h_, qi, ki: (b_, qi, h_, 0)),
            pl.BlockSpec((1, bk_, 1, hd), lambda b_, h_, qi, ki: (b_, ki, h_ // g, 0)),
            pl.BlockSpec((1, bk_, 1, hd), lambda b_, h_, qi, ki: (b_, ki, h_ // g, 0)),
            pl.BlockSpec((1, bq_, 1, hd), lambda b_, h_, qi, ki: (b_, qi, h_, 0)),
            pl.BlockSpec((1, 1, 1, bq_), lambda b_, h_, qi, ki: (b_, 0, h_, qi)),
            pl.BlockSpec((1, 1, 1, bq_), lambda b_, h_, qi, ki: (b_, 0, h_, qi)),
        ],
        out_specs=pl.BlockSpec((1, bq_, 1, hd), lambda b_, h_, qi, ki: (b_, qi, h_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, h, hd), jnp.float32),
        interpret=interpret,
    )(q_pos, k_pos, q, k, v, do, lse4, dd4)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, window=window,
                          softcap=softcap, scale=scale, g=g, nq=nq),
        grid=(b, kv, nk, g, nq),
        in_specs=[
            pl.BlockSpec((1, bq_), lambda b_, kv_, ki, gi, qi: (b_, qi)),
            pl.BlockSpec((1, bk_), lambda b_, kv_, ki, gi, qi: (b_, ki)),
            pl.BlockSpec((1, bq_, 1, hd),
                         lambda b_, kv_, ki, gi, qi: (b_, qi, kv_ * g + gi, 0)),
            pl.BlockSpec((1, bk_, 1, hd), lambda b_, kv_, ki, gi, qi: (b_, ki, kv_, 0)),
            pl.BlockSpec((1, bk_, 1, hd), lambda b_, kv_, ki, gi, qi: (b_, ki, kv_, 0)),
            pl.BlockSpec((1, bq_, 1, hd),
                         lambda b_, kv_, ki, gi, qi: (b_, qi, kv_ * g + gi, 0)),
            pl.BlockSpec((1, 1, 1, bq_),
                         lambda b_, kv_, ki, gi, qi: (b_, 0, kv_ * g + gi, qi)),
            pl.BlockSpec((1, 1, 1, bq_),
                         lambda b_, kv_, ki, gi, qi: (b_, 0, kv_ * g + gi, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk_, 1, hd), lambda b_, kv_, ki, gi, qi: (b_, ki, kv_, 0)),
            pl.BlockSpec((1, bk_, 1, hd), lambda b_, kv_, ki, gi, qi: (b_, ki, kv_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, kv, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, t, kv, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q_pos, k_pos, q, k, v, do, lse4, dd4)

    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None)


# --------------------------------------------------------------- public API
@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11)
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    k_pos: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: float = 1.0,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """q (B,S,H,hd), k/v (B,T,KV,hd), positions (B,S)/(B,T) -> (B,S,H,hd) f32.

    ``interpret=None`` resolves through the engine's shared backend policy.
    """
    o, _ = _fwd(q, k, v, q_pos, k_pos, causal, window, softcap, scale, bq, bk,
                resolve_interpret(interpret))
    return o


def _fwd_vjp(q, k, v, q_pos, k_pos, causal, window, softcap, scale, bq, bk,
             interpret):
    o, lse = _fwd(q, k, v, q_pos, k_pos, causal, window, softcap, scale, bq, bk,
                  resolve_interpret(interpret))
    return o, (q, k, v, q_pos, k_pos, o, lse)


def _bwd_vjp(causal, window, softcap, scale, bq, bk, interpret, res, do):
    return _bwd(causal, window, softcap, scale, bq, bk,
                resolve_interpret(interpret), res, do)


flash_attention.defvjp(_fwd_vjp, _bwd_vjp)


# ------------------------------------------------------------- flash decode
def _decode_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, o_ref, ml_ref,
                   *, window, softcap, scale, nk, g):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        ml_ref[0, 0, 0, :] = jnp.full((g,), NEG_INF, jnp.float32)
        ml_ref[0, 1, 0, :] = jnp.zeros((g,), jnp.float32)

    q = q_ref[0, 0, :, :].astype(jnp.float32)   # (g, hd) — the KV group's heads
    k = k_ref[0, :, 0, :].astype(jnp.float32)   # (bk, hd)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    s = _dot(q, k, trans_b=True) * scale        # (g, bk)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qp = qp_ref[0]                               # scalar decode position
    kp = kp_ref[0, :]
    allow = (kp >= 0) & (kp <= qp)
    if window is not None:
        allow &= qp - kp < window
    s = jnp.where(allow[None, :], s, NEG_INF)

    m_prev = ml_ref[0, 0, 0, :]
    l_prev = ml_ref[0, 1, 0, :]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    ml_ref[0, 1, 0, :] = l_prev * corr + p.sum(axis=-1)
    ml_ref[0, 0, 0, :] = m_new
    o_ref[0, 0, :, :] = o_ref[0, 0, :, :] * corr[:, None] + _dot(p, v)

    @pl.when(ki == nk - 1)
    def _():
        l = jnp.maximum(ml_ref[0, 1, 0, :], 1e-30)
        o_ref[0, 0, :, :] = o_ref[0, 0, :, :] / l[:, None]


def flash_decode(
    q: jax.Array,      # (B, H, hd) — one new token per sequence
    k: jax.Array,      # (B, T, KV, hd) full cache
    v: jax.Array,
    q_pos: jax.Array,  # (B,) int32 decode positions
    k_pos: jax.Array,  # (B, T) int32 (-1 = unwritten slot)
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: float = 1.0,
    bk: int = DEFAULT_BK,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Decode-step attention with the KV cache streamed through VMEM.

    The grid iterates (batch, kv-head, key-block); each kv head's g query
    heads form the row dim of the MXU tile, so GQA needs no HBM repeat.
    Returns (B, H, hd) f32.  ``interpret=None`` resolves through the
    engine's shared backend policy.
    """
    b, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    bk = min(bk, t)
    nk = pl.cdiv(t, bk)
    qg = q.reshape(b, kv, g, hd)

    o, _ = pl.pallas_call(
        functools.partial(_decode_kernel, window=window, softcap=softcap,
                          scale=scale, nk=nk, g=g),
        grid=(b, kv, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda b_, kv_, ki: (b_,)),
            pl.BlockSpec((1, bk), lambda b_, kv_, ki: (b_, ki)),
            pl.BlockSpec((1, 1, g, hd), lambda b_, kv_, ki: (b_, kv_, 0, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b_, kv_, ki: (b_, ki, kv_, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b_, kv_, ki: (b_, ki, kv_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda b_, kv_, ki: (b_, kv_, 0, 0)),
            pl.BlockSpec((1, 2, 1, g), lambda b_, kv_, ki: (b_, 0, kv_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, g, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, 2, kv, g), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q_pos, k_pos, qg, k, v)
    return o.reshape(b, h, hd)
