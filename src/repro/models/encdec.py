"""Encoder–decoder backbone (Seamless-M4T family).

Encoder: non-causal self-attention stack over precomputed *frame
embeddings* (the modality frontend is a stub per the assignment — inputs
arrive as (B, S_src, d_model) conformer-frame embeddings).

Decoder: causal self-attention + cross-attention over the encoder memory
+ gated FFN, with a self-attn KV cache for decode and a *cross-KV cache*
computed once from the memory (the per-step cross K/V projections would
otherwise dominate decode FLOPs — this is the enc-dec analogue of the
paper keeping the shift registers out of the approximated datapath).

Both stacks are scanned over stacked per-layer parameters, like
``models.transformer``.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import DP, TP, constrain
from repro.models import attention, layers
from repro.models.attention import KVCache
from repro.models.layers import Ctx

__all__ = [
    "DecCache",
    "init_params",
    "encode",
    "decode_forward",
    "init_dec_caches",
    "precompute_cross",
]


class DecCache(NamedTuple):
    self_kv: KVCache  # (B, S_max, KV, hd) causal self-attn cache
    cross_k: jax.Array  # (B, S_mem, KV, hd) fixed after precompute
    cross_v: jax.Array


# ----------------------------------------------------------------- params
def _init_enc_block(key, cfg: ModelConfig, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "attn": attention.init_attn(k1, cfg, dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "ffn": layers.init_mlp(k2, cfg, dtype),
    }


def _init_dec_block(key, cfg: ModelConfig, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "attn": attention.init_attn(k1, cfg, dtype),
        "ln_cross": jnp.zeros((cfg.d_model,), dtype),
        "cross": {
            "cross_wq": layers.init_dense(k2, cfg.d_model, cfg.num_heads * cfg.head_dim, dtype),
            "cross_wk": layers.init_dense(
                jax.random.fold_in(k2, 1), cfg.d_model, cfg.num_kv_heads * cfg.head_dim, dtype
            ),
            "cross_wv": layers.init_dense(
                jax.random.fold_in(k2, 2), cfg.d_model, cfg.num_kv_heads * cfg.head_dim, dtype
            ),
            "cross_wo": layers.init_dense(
                jax.random.fold_in(k2, 3), cfg.num_heads * cfg.head_dim, cfg.d_model, dtype
            ),
        },
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "ffn": layers.init_mlp(k3, cfg, dtype),
    }


def init_params(key, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    ke, kh, kenc, kdec = jax.random.split(key, 4)
    params: dict = {
        "embed": (
            jax.random.normal(ke, (cfg.vocab_size, cfg.d_model), jnp.float32)
            * cfg.d_model**-0.5
        ).astype(dtype),
        "enc_final_norm": jnp.zeros((cfg.d_model,), dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.init_dense(kh, cfg.d_model, cfg.vocab_size, dtype)
    enc_keys = jax.random.split(kenc, cfg.encoder_layers)
    dec_keys = jax.random.split(kdec, cfg.num_layers)
    params["enc_scan"] = jax.vmap(lambda k: _init_enc_block(k, cfg, dtype))(enc_keys)
    params["dec_scan"] = jax.vmap(lambda k: _init_dec_block(k, cfg, dtype))(dec_keys)
    return params


# ------------------------------------------------------------------ remat
def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    policy = (
        jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        if cfg.remat == "dots"
        else jax.checkpoint_policies.nothing_saveable
    )
    return jax.checkpoint(fn, policy=policy)


# ---------------------------------------------------------------- encoder
def encode(params: dict, src_embeds: jax.Array, src_pos: jax.Array, ctx: Ctx) -> jax.Array:
    """src_embeds: (B, S_src, D) frame embeddings -> memory (B, S_src, D)."""
    cfg = ctx.cfg
    x = src_embeds.astype(jnp.dtype(cfg.dtype))
    x = constrain(x, DP, None, None)

    def body(x, p):
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        out, _ = attention.attention(p["attn"], h, src_pos, ctx, causal=False)
        x = x + out
        h2 = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + layers.mlp(p["ffn"], h2, ctx)
        return constrain(x, DP, None, None), None

    x, _ = jax.lax.scan(_remat(body, cfg), x, params["enc_scan"])
    return layers.rms_norm(x, params["enc_final_norm"], cfg.norm_eps)


# ------------------------------------------------------------- cross attn
def _cross_attend(p: dict, x: jax.Array, mem_pos: jax.Array,
                  ck: jax.Array, cv: jax.Array, ctx: Ctx) -> jax.Array:
    """Cross-attention against precomputed cross K/V (B, S_mem, KV, hd)."""
    cfg = ctx.cfg
    b, s, _ = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = layers.dense(x, p["cross_wq"], ctx, "attn").reshape(b, s, h, hd)
    q = constrain(q, DP, None, TP, None)
    k, v = ck, cv
    if h // kvh > 1:
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    q_pos = jnp.zeros((b, s), jnp.int32)
    out = attention._attend_direct(
        q, k, v, q_pos, mem_pos, causal=False, window=None,
        softcap=None, scale=hd**-0.5,
    )
    out = out.reshape(b, s, h * hd).astype(x.dtype)
    out = constrain(out, DP, None, TP)
    return layers.dense(out, p["cross_wo"], ctx, "attn")


def precompute_cross(params: dict, memory: jax.Array, ctx: Ctx) -> tuple[jax.Array, jax.Array]:
    """Stacked (L, B, S_mem, KV, hd) cross K/V from the encoder memory."""
    cfg = ctx.cfg
    b, sm, _ = memory.shape
    kvh, hd = cfg.num_kv_heads, cfg.head_dim

    def one(p):
        ck = layers.dense(memory, p["cross"]["cross_wk"], ctx, "attn").reshape(b, sm, kvh, hd)
        cv = layers.dense(memory, p["cross"]["cross_wv"], ctx, "attn").reshape(b, sm, kvh, hd)
        return ck, cv

    return jax.lax.map(one, params["dec_scan"])


# ---------------------------------------------------------------- decoder
def init_dec_caches(cfg: ModelConfig, batch: int, max_seq: int, mem_len: int, dtype) -> DecCache:
    """Stacked (L, ...) decoder caches (self KV + cross KV slots)."""
    kv = (batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
    xkv = (batch, mem_len, cfg.num_kv_heads, cfg.head_dim)
    L = cfg.num_layers

    def stack(shape):
        return jnp.zeros((L,) + shape, dtype)

    return DecCache(
        self_kv=KVCache(stack(kv), stack(kv)),
        cross_k=stack(xkv),
        cross_v=stack(xkv),
    )


def decode_forward(
    params: dict,
    tokens: jax.Array,
    positions: jax.Array,
    mem_pos: jax.Array,
    ctx: Ctx,
    *,
    memory: Optional[jax.Array] = None,
    caches: Optional[DecCache] = None,
    cache_pos=None,
) -> tuple[jax.Array, Optional[DecCache]]:
    """Decoder forward.  Either ``memory`` (training/prefill: cross K/V are
    computed on the fly) or ``caches`` with precomputed cross K/V must be
    given.  Returns (hidden, new_caches)."""
    cfg = ctx.cfg
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
    x = constrain(x, DP, None, None)
    b, s, _ = x.shape
    kvh, hd = cfg.num_kv_heads, cfg.head_dim

    def body(carry, xs):
        x = carry
        if caches is not None:
            p, skv, ck, cv = xs
        else:
            p = xs
            skv = ck = cv = None
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        out, new_skv = attention.attention(
            p["attn"], h, positions, ctx, cache=skv, cache_pos=cache_pos
        )
        x = x + out
        hc = layers.rms_norm(x, p["ln_cross"], cfg.norm_eps)
        if caches is not None:
            x = x + _cross_attend(p["cross"], hc, mem_pos, ck, cv, ctx)
        else:
            mk = layers.dense(memory, p["cross"]["cross_wk"], ctx, "attn").reshape(
                b, memory.shape[1], kvh, hd
            )
            mv = layers.dense(memory, p["cross"]["cross_wv"], ctx, "attn").reshape(
                b, memory.shape[1], kvh, hd
            )
            x = x + _cross_attend(p["cross"], hc, mem_pos, mk, mv, ctx)
        h2 = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + layers.mlp(p["ffn"], h2, ctx)
        x = constrain(x, DP, None, None)
        return x, new_skv

    if caches is not None:
        x, new_skv = jax.lax.scan(
            _remat(body, cfg), x,
            (params["dec_scan"], caches.self_kv, caches.cross_k, caches.cross_v),
        )
        if s == 1:  # decode: the scan handed back only the new entries
            new_skv = attention.write_kv(caches.self_kv, new_skv, cache_pos)
        new_caches = DecCache(new_skv, caches.cross_k, caches.cross_v)
    else:
        x, _ = jax.lax.scan(_remat(body, cfg), x, params["dec_scan"])
        new_caches = None

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, new_caches
