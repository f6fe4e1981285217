"""Decoder-only transformer stack: scanned layer groups, mixed block kinds.

The layer pattern (e.g. gemma2's ("attn_local", "attn_global"), griffin's
("rglru", "rglru", "attn_local")) defines a *group*; ``num_layers //
len(pattern)`` groups are evaluated under one ``jax.lax.scan`` over
stacked parameters (compile time and HLO size stay O(group), not
O(depth)), with any remainder layers unrolled.  Remat (configurable
policy) wraps the group body.

An ``attn_ssd`` block (Falcon-H1) runs global attention and an SSD mixer
side by side on one normed input and adds their outputs before the MLP;
its cache is a :class:`HybridCache`, a KV cache and an SSD state side by
side.

Caches (KV / RG-LRU / SSD states) are pytrees stacked the same way and
threaded through the scan as (xs -> ys).  A decode step (one token over
caches) is the exception.  The scan reads the stacked KV pool as ``xs``
without rewriting it, emits each layer's new (B, 1, KV, hd) k/v as
``ys``, and one ``attention.write_kv`` after the scan writes all of them
into the (donated) pool.  Recurrent state (conv and SSM / RG-LRU state),
which a decode step reads and rewrites whole, rides in the scan's carry:
each layer reads its slice at the scan index and writes its new state
back in place, so the carried stack is the donated pool itself.

The forward pass returns final *hidden states*; logits are produced by
``lm_head()`` (or, in training, never fully materialized — the loss is
computed in vocab-chunked form, see train/steps.py).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import DP, TP, constrain
from repro.models import attention, layers, moe, rglru, ssd
from repro.models.layers import Ctx

__all__ = [
    "HybridCache",
    "init_params",
    "init_caches",
    "forward",
    "lm_head",
    "block_kinds",
    "state_bytes",
]


class HybridCache(NamedTuple):
    """An ``attn_ssd`` layer's cache: its KV cache and its SSD state."""

    kv: attention.KVCache
    ssd: ssd.SSDCache


def block_kinds(cfg: ModelConfig) -> list[str]:
    pat = cfg.layer_pattern
    return [pat[i % len(pat)] for i in range(cfg.num_layers)]


def _has_ffn(cfg: ModelConfig, kind: str) -> bool:
    if kind == "ssd":
        return cfg.d_ff > 0
    return cfg.d_ff > 0 or cfg.num_experts > 0


def _init_block(key, cfg: ModelConfig, kind: str, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p: dict = {"ln1": jnp.zeros((cfg.d_model,), dtype)}
    if kind in ("attn_global", "attn_local"):
        p["attn"] = attention.init_attn(k1, cfg, dtype)
    elif kind == "rglru":
        p["rglru"] = rglru.init_rglru(k1, cfg, dtype)
    elif kind == "ssd":
        p["ssd"] = ssd.init_ssd(k1, cfg, dtype)
    elif kind == "attn_ssd":
        p["attn"] = attention.init_attn(k1, cfg, dtype)
        p["ssd"] = ssd.init_ssd(k3, cfg, dtype)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    if cfg.use_post_norm:
        p["post_ln1"] = jnp.zeros((cfg.d_model,), dtype)
    if _has_ffn(cfg, kind):
        p["ln2"] = jnp.zeros((cfg.d_model,), dtype)
        if cfg.num_experts > 0:
            p["ffn_moe"] = moe.init_moe(k2, cfg, dtype)
        else:
            p["ffn"] = layers.init_mlp(k2, cfg, dtype)
        if cfg.use_post_norm:
            p["post_ln2"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def _apply_block(
    params: dict,
    kind: str,
    x: jax.Array,
    positions: jax.Array,
    ctx: Ctx,
    cache: Any,
    cache_pos,
) -> tuple[jax.Array, Any, jax.Array]:
    cfg = ctx.cfg
    h = layers.rms_norm(x, params["ln1"], cfg.norm_eps)
    if kind in ("attn_global", "attn_local"):
        with jax.named_scope("attn"):
            out, new_cache = attention.attention(
                params["attn"], h, positions, ctx,
                local=(kind == "attn_local"), cache=cache, cache_pos=cache_pos,
            )
    elif kind == "rglru":
        out, new_cache = rglru.rglru_block(params["rglru"], h, ctx, cache=cache)
    elif kind == "ssd":
        out, new_cache = ssd.ssd_block(params["ssd"], h, ctx, cache=cache,
                                       positions=positions)
    elif kind == "attn_ssd":
        with jax.named_scope("attn"):
            a_out, kv = attention.attention(
                params["attn"], h, positions, ctx,
                cache=None if cache is None else cache.kv, cache_pos=cache_pos,
            )
        m_out, state = ssd.ssd_block(params["ssd"], h, ctx,
                                     cache=None if cache is None else cache.ssd,
                                     positions=positions)
        with jax.named_scope("hybrid"):
            out = m_out + a_out
        new_cache = None if cache is None else HybridCache(kv, state)
    else:
        raise ValueError(kind)
    if cfg.use_post_norm:
        out = layers.rms_norm(out, params["post_ln1"], cfg.norm_eps)
    x = x + out
    aux = jnp.float32(0.0)
    if _has_ffn(cfg, kind):
        h2 = layers.rms_norm(x, params["ln2"], cfg.norm_eps)
        if cfg.num_experts > 0:
            out2, aux = moe.moe_ffn(params["ffn_moe"], h2, ctx)
        else:
            with jax.named_scope("mlp"):
                out2 = layers.mlp(params["ffn"], h2, ctx)
        if cfg.use_post_norm:
            out2 = layers.rms_norm(out2, params["post_ln2"], cfg.norm_eps)
        x = x + out2
    if cfg.seq_shard_residuals:
        x = constrain(x, DP, TP, None)  # sequence-parallel residual stream
    else:
        x = constrain(x, DP, None, None)
    return x, new_cache, aux


def _init_cache_for(cfg: ModelConfig, kind: str, batch: int, max_seq: int, dtype):
    if kind in ("attn_global", "attn_local"):
        return attention.init_kv_cache(cfg, batch, max_seq, dtype)
    if kind == "attn_ssd":
        return HybridCache(attention.init_kv_cache(cfg, batch, max_seq, dtype),
                           ssd.init_ssd_cache(cfg, batch, dtype))
    if kind == "rglru":
        return rglru.init_rglru_cache(cfg, batch, dtype)
    if kind == "ssd":
        return ssd.init_ssd_cache(cfg, batch, dtype)
    raise ValueError(kind)


def _commit(kind: str, cache: Any, out: Any, cache_pos, decode: bool):
    """A layer's cache after the forward.  In a decode step an attention
    layer hands back only the new token's k/v, written here; every other
    layer and step hands back its whole new cache (a hybrid layer, its
    whole new SSD state beside its new k/v; in the layer scan, recurrent
    state was already written into the carried pool)."""
    if decode and kind == "attn_ssd":
        return HybridCache(attention.write_kv(cache.kv, out.kv, cache_pos), out.ssd)
    if decode and kind in ("attn_global", "attn_local"):
        return attention.write_kv(cache, out, cache_pos)
    return out


def _is_recurrent(cache) -> bool:
    return isinstance(cache, (ssd.SSDCache, rglru.RGLRUCache))


def _split_recurrent(caches) -> tuple[Any, Any]:
    """(caches with each recurrent node set to None, the recurrent nodes
    with every other leaf set to None): two pytrees that
    ``_join_recurrent`` puts back together; (caches, None) where there is
    no recurrent node."""
    nodes = jax.tree_util.tree_leaves(caches, is_leaf=_is_recurrent)
    if not any(_is_recurrent(c) for c in nodes):
        return caches, None

    def keep(want):
        return lambda c: c if _is_recurrent(c) == want else None

    rest = jax.tree_util.tree_map(keep(False), caches, is_leaf=_is_recurrent)
    return rest, jax.tree_util.tree_map(keep(True), caches, is_leaf=_is_recurrent)


def _join_recurrent(rest, recurrent):
    return jax.tree_util.tree_map(lambda r, c: c if r is None else r, recurrent, rest,
                                  is_leaf=lambda c: c is None or _is_recurrent(c))


def state_bytes(caches) -> int:
    """Bytes of recurrent state (conv and SSM / RG-LRU state, every layer,
    every row) in a cache pytree, KV caches left out; 0 for attention-only
    stacks."""
    nodes = jax.tree_util.tree_leaves(caches, is_leaf=_is_recurrent)
    return sum(a.size * a.dtype.itemsize for c in nodes if _is_recurrent(c) for a in c)


# --------------------------------------------------------------------- init
def init_params(key, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    kinds = block_kinds(cfg)
    period = len(cfg.layer_pattern)
    repeats = cfg.num_layers // period if cfg.scan_layers else 0
    rem_kinds = kinds[repeats * period :]

    ke, kh, kb = jax.random.split(key, 3)
    params: dict = {
        "embed": (
            jax.random.normal(ke, (cfg.vocab_size, cfg.d_model), jnp.float32)
            * cfg.d_model**-0.5
        ).astype(dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.init_dense(kh, cfg.d_model, cfg.vocab_size, dtype)

    if repeats:
        def init_group(gkey):
            sub = jax.random.split(gkey, period)
            return {f"sub{i}": _init_block(sub[i], cfg, cfg.layer_pattern[i], dtype)
                    for i in range(period)}

        gkeys = jax.random.split(kb, repeats + 1)
        stacked = jax.vmap(init_group)(gkeys[:repeats])
        params["scan"] = stacked
        rem_key = gkeys[repeats]
    else:
        rem_key = kb
    if rem_kinds:
        rkeys = jax.random.split(rem_key, len(rem_kinds))
        params["rem"] = [
            _init_block(rkeys[i], cfg, kind, dtype) for i, kind in enumerate(rem_kinds)
        ]
    return params


def init_caches(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> dict:
    kinds = block_kinds(cfg)
    period = len(cfg.layer_pattern)
    repeats = cfg.num_layers // period if cfg.scan_layers else 0
    rem_kinds = kinds[repeats * period :]
    caches: dict = {}
    if repeats:
        def one_group(_):
            return {
                f"sub{i}": _init_cache_for(cfg, cfg.layer_pattern[i], batch, max_seq, dtype)
                for i in range(period)
            }

        caches["scan"] = jax.vmap(one_group)(jnp.arange(repeats))
    if rem_kinds:
        caches["rem"] = [
            _init_cache_for(cfg, kind, batch, max_seq, dtype) for kind in rem_kinds
        ]
    return caches


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


# ------------------------------------------------------------------ forward
def forward(
    params: dict,
    tokens: Optional[jax.Array],
    positions: jax.Array,
    ctx: Ctx,
    *,
    embeds: Optional[jax.Array] = None,
    caches: Optional[dict] = None,
    cache_pos=None,
) -> tuple[jax.Array, Optional[dict], jax.Array]:
    """Returns (hidden (B, S, D), new_caches, aux_loss)."""
    cfg = ctx.cfg
    if embeds is None:
        x = jnp.take(params["embed"], tokens, axis=0)
    else:
        x = embeds.astype(jnp.dtype(cfg.dtype))
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
    x = layers.scale(x, cfg.embed_multiplier)
    x = constrain(x, DP, TP if cfg.seq_shard_residuals else None, None)

    period = len(cfg.layer_pattern)
    repeats = cfg.num_layers // period if cfg.scan_layers else 0
    new_caches: dict = {}
    decode = caches is not None and x.shape[1] == 1

    if repeats:
        def group_body(carry, xs):
            x, aux, state = carry
            gparams, gcache, layer = xs
            if state is not None:
                # a decode step: this group's recurrent state, read from the carry
                gcache = _join_recurrent(gcache, jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
                    state))
            out = {}
            for i in range(period):
                kind = cfg.layer_pattern[i]
                sub_cache = gcache[f"sub{i}"] if gcache is not None else None
                x, out[f"sub{i}"], a = _apply_block(
                    gparams[f"sub{i}"], kind, x, positions, ctx, sub_cache, cache_pos
                )
                aux = aux + a
            if state is not None:
                # ...and written back into it in place
                out, new_state = _split_recurrent(out)
                state = jax.tree_util.tree_map(
                    lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, layer, 0),
                    state, new_state)
            return (x, aux, state), (out if gcache is not None else None)

        body = _remat(group_body, cfg)
        scan_caches = caches.get("scan") if caches else None
        if scan_caches is None:
            # keep xs pytree structure static: pass params only
            (x, aux, _), _ = jax.lax.scan(
                lambda c, p: (body(c, (p, None, None))[0], None),
                (x, jnp.float32(0.0), None),
                params["scan"],
            )
        else:
            read, state = _split_recurrent(scan_caches) if decode else (scan_caches, None)
            (x, aux, state), outs = jax.lax.scan(
                body, (x, jnp.float32(0.0), state),
                (params["scan"], read, None if state is None else jnp.arange(repeats)),
            )
            if state is not None:
                outs = _join_recurrent(outs, state)
            new_caches["scan"] = {
                f"sub{i}": _commit(kind, scan_caches[f"sub{i}"], outs[f"sub{i}"],
                                   cache_pos, decode)
                for i, kind in enumerate(cfg.layer_pattern)
            }
    else:
        aux = jnp.float32(0.0)

    kinds = block_kinds(cfg)
    rem_kinds = kinds[repeats * period :]
    for i, kind in enumerate(rem_kinds):
        rcache = caches["rem"][i] if caches and "rem" in caches else None
        x, nc, a = _apply_block(
            params["rem"][i], kind, x, positions, ctx, rcache, cache_pos
        )
        aux = aux + a
        if rcache is not None:
            new_caches.setdefault("rem", [None] * len(rem_kinds))[i] = _commit(
                kind, rcache, nc, cache_pos, decode)

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, (new_caches if caches else None), aux


def lm_head(params: dict, hidden: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Full logits (B, S, V).  Use only for small S (decode / smoke tests)."""
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", hidden.astype(jnp.float32), w.astype(jnp.float32))
    logits = layers.scale(logits, cfg.lm_head_multiplier)
    if cfg.final_logit_softcap:
        logits = jnp.tanh(logits / cfg.final_logit_softcap) * cfg.final_logit_softcap
    logits = constrain(logits, DP, None, TP)
    return logits
