"""The plain reference: the published decoder in float32, and its control.

A Qwen3 / Llama-style decoder written from the published description in
plain ``jax.numpy``: RMSNorm (weight ``w``), grouped-query attention with
rotate-half RoPE and, where the configuration says so, RMSNorm on each
query and key head; SwiGLU feed-forward; final RMSNorm and the head
(tied to the embedding where the configuration says so).  Every matrix
product runs at ``Precision.HIGHEST``.  It imports nothing of the program
and makes its own weights from the seed (``perfbench.weights``).

It runs layer by layer over a batch of sequences (prompt + served
tokens, teacher-forced), so that one layer's float32 weights are all it
holds of the model at once.

The control is the same reference with every weight product computed in
int8 (per-output-channel weight scales, per-token activation scales,
int32 sums): the step below bfloat16 that would tempt a later change.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights

HIGHEST = jax.lax.Precision.HIGHEST


def _mm_f32(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _mm_int8(x, w):
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 127.0
    sw = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30) / 127.0
    xq = jnp.round(x / sx).astype(jnp.int8)
    wq = jnp.round(w / sw).astype(jnp.int8)
    acc = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (T, H, hd); rotate-half RoPE at positions ``pos`` (T,)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _block(cfg, mm, h, w):
    """One decoder layer on one sequence h (T, D); w in float32, norms
    already as published weights (1 + delta)."""
    t = h.shape[0]
    nh, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(t)
    x = _rms(h, w["ln1"], eps)
    q = mm(x, w["q"]).reshape(t, nh, hd)
    k = mm(x, w["k"]).reshape(t, nkv, hd)
    v = mm(x, w["v"]).reshape(t, nkv, hd)
    if cfg["qk_norm"]:
        q = _rms(q, w["q_norm"], eps)
        k = _rms(k, w["k_norm"], eps)
    q = _rope(q, pos, cfg["rope_theta"])
    k = _rope(k, pos, cfg["rope_theta"])
    g = nh // nkv  # query head i reads key/value head i // g
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(t, nh * hd)
    h = h + mm(a, w["o"])
    x = _rms(h, w["ln2"], eps)
    return h + mm(jax.nn.silu(mm(x, w["gate"])) * mm(x, w["up"]), w["down"])


def _f32_layer(w):
    out = {name: a.astype(jnp.float32) for name, a in w.items()}
    for name in ("ln1", "ln2", "q_norm", "k_norm"):
        if name in out:
            out[name] = 1.0 + out[name]
    return out


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(cfg_items, control, key, layer, hs):
    """Layer ``layer`` over every sequence, for the reference stream and,
    with ``control``, the int8 stream."""
    cfg = dict(cfg_items)
    w = _f32_layer(weights.layer_weights(cfg, key, layer))
    ref = jax.lax.map(lambda h: _block(cfg, _mm_f32, h, w), hs[0])
    if not control:
        return (ref,)
    return ref, jax.lax.map(lambda h: _block(cfg, _mm_int8, h, w), hs[1])


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(cfg_items, key, tokens):
    return jnp.take(weights.embedding(dict(cfg_items), key), tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head_gaps(cfg_items, control, key, hs, seq, pos, target):
    """Gap below the reference's best logit of each target token and, with
    ``control``, of the token the int8 stream puts first."""
    cfg = dict(cfg_items)
    if cfg["tie_word_embeddings"]:
        wh = weights.embedding(cfg, key).astype(jnp.float32).T
    else:
        wh = weights.head(cfg, key).astype(jnp.float32)
    norm = 1.0 + weights.final_norm(cfg, key).astype(jnp.float32)
    eps = cfg["rms_norm_eps"]
    ref = _mm_f32(_rms(hs[0][seq, pos], norm, eps), wh)
    best = ref.max(axis=-1)
    out = (best - jnp.take_along_axis(ref, target[:, None], axis=-1)[:, 0],)
    if control:
        top = jnp.argmax(_mm_int8(_rms(hs[1][seq, pos], norm, eps), wh), axis=-1)
        out += (best - jnp.take_along_axis(ref, top[:, None], axis=-1)[:, 0],)
    return out


def gaps(cfg: dict, seed: int, prompts, served, length: int, *, control=False):
    """Per served token, the gap by which its reference logit lies below the
    reference's best (and, with ``control``, the same gap of the int8
    stream's first choice at that position).

    ``prompts[i]`` and ``served[i]`` are one request; the reference runs
    over ``prompt + served[:-1]``, right-padded to ``length`` so that one
    compiled program serves every run of a cell.  Returns
    ``{"served": array, "control": array | None}``.
    """
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items() if not isinstance(v, (dict, list))))
    key = weights.base_key(seed)
    tokens = np.zeros((len(prompts), length), np.int32)
    seq, pos, target = [], [], []
    for i, (p, s) in enumerate(zip(prompts, served)):
        full = np.concatenate([np.asarray(p, np.int32), np.asarray(s, np.int32)])[:-1]
        if len(full) > length:
            raise ValueError(f"sequence of {len(full)} tokens exceeds {length}")
        tokens[i, : len(full)] = full
        seq += [i] * len(s)
        pos += list(range(len(p) - 1, len(p) - 1 + len(s)))
        target += [int(t) for t in s]
    h = _embed(cfg_items, key, jnp.asarray(tokens))
    hs = (h, h) if control else (h,)
    for layer in range(cfg["num_hidden_layers"]):
        hs = _layer(cfg_items, control, key, jnp.int32(layer), hs)
    out = _head_gaps(cfg_items, control, key, hs, jnp.asarray(seq, jnp.int32),
                     jnp.asarray(pos, jnp.int32), jnp.asarray(target, jnp.int32))
    out = [np.asarray(o, np.float64) for o in out]
    return {"served": out[0], "control": out[1] if control else None}
