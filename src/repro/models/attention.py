"""Multi-head attention: GQA/MQA, RoPE/M-RoPE, qk-norm, logit softcaps,
sliding-window (local) masking, muP multipliers on the input, the keys and
the output (applied where not 1), and a KV cache for prefill + decode.

Tensor-parallel layout: prefill and training attend on a *flat* head axis
H = KV * G (k/v are repeated from KV to H at use — the cache stays
unrepeated), so a single ``model``-axis constraint shards the whole
computation whenever H divides the axis (true for 8/10 assigned archs at
model=16; qwen2-vl H=28 and recurrentgemma H=10 replicate and are flagged
in EXPERIMENTS.md).

Prefill / training uses a blockwise online-softmax (flash-style)
formulation: an outer ``lax.map`` over query chunks and an inner
``lax.scan`` over key chunks carrying (running max, denominator,
accumulator) — peak live logits are (B, H, q_chunk, k_chunk) instead of
(B, H, S, T).  A forward of S > 1 tokens over a cache (admission
prefill, speculative verify) writes its k/v into the cache first and
attends over the written cache.

Decode (s == 1 over a cache) reads the cache in place and does not write
it: the query, grouped as (B, KV, G, hd), is contracted against the
unrepeated cache in its own dtype with float32 accumulation, the write
slot masked out; the new token's own key and value join the softmax
beside the T cache scores, and the output is ``p_cache @ V_cache +
p_new * v_new``.  ``attention`` then returns the new token's k/v as the
cache, and the caller writes the (layers, B) new entries into the pool
with one scatter (:func:`write_kv`) after its layer scan — the pool is
never copied or rewritten whole.  The cache *sequence* axis is sharded
over the model axis (flash-decode style): per-device partial logits over
T/|model| keys, with the softmax max/sum reductions lowering to
all-reduces — this is what makes decode_32k × batch 128 fit.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import DP, TP, constrain
from repro.models import layers
from repro.models.layers import Ctx

__all__ = ["KVCache", "init_attn", "attention", "init_kv_cache", "write_kv"]

NEG_INF = -2.3819763e38  # bf16-safe large negative
Q_CHUNK = 1024
K_CHUNK = 1024


class KVCache(NamedTuple):
    k: jax.Array  # (B, S_max, KV, hd)
    v: jax.Array


def _row_update(cache: jax.Array, update: jax.Array, starts: jax.Array) -> jax.Array:
    """Per-row dynamic_update_slice along the cache sequence axis.

    cache (B, T, KV, hd), update (B, S, KV, hd), starts (B,) int32: row i's
    update lands at sequence offset starts[i].  Lowered as a batched
    scatter, this is what lets continuous-batching slots sit at different
    depths of the same physical cache.
    """
    def one(c, u, p):
        return jax.lax.dynamic_update_slice(c, u, (p, 0, 0))

    return jax.vmap(one)(cache, update, starts)


def write_kv(cache: KVCache, new: KVCache, cache_pos) -> KVCache:
    """Write a decode step's new entries into a cache, in place.

    ``cache`` leaves are (..., B, T, KV, hd), ``new`` leaves (..., B, 1, KV,
    hd) with the same leading (stacked-layer) axes.  A scalar ``cache_pos``
    is one ``dynamic_update_slice``; a per-row ``(B,)`` vector is one
    scatter of (KV, hd) windows at (leading..., row, cache_pos[row]),
    clamped into range as ``dynamic_update_slice`` clamps.  Indexing every
    axis but the last two keeps each window contiguous in the cache's own
    layout, so the scatter writes in place.
    """
    pos = jnp.asarray(cache_pos, jnp.int32)

    def put(c, u):
        lead = c.ndim - 4
        u = u.astype(c.dtype)
        if pos.ndim == 0:
            return jax.lax.dynamic_update_slice(c, u, (0,) * (lead + 1) + (pos, 0, 0))
        n = lead + 1  # the leading axes and the row axis
        idx = tuple(
            jnp.arange(c.shape[i], dtype=jnp.int32).reshape(
                [-1 if j == i else 1 for j in range(n)])
            for i in range(n)
        ) + (pos.reshape([1] * lead + [-1]),)
        return c.at[idx].set(u[..., 0, :, :], mode="clip",
                             unique_indices=True, indices_are_sorted=True)

    with jax.named_scope("attn"), jax.named_scope("cache_update"):
        return KVCache(put(cache.k, new.k), put(cache.v, new.v))


def init_attn(key, cfg: ModelConfig, dtype, cross: bool = False) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": layers.init_dense(kq, cfg.d_model, cfg.num_heads * cfg.head_dim, dtype),
        "wk": layers.init_dense(kk, cfg.d_model, cfg.num_kv_heads * cfg.head_dim, dtype),
        "wv": layers.init_dense(kv, cfg.d_model, cfg.num_kv_heads * cfg.head_dim, dtype),
        "wo": layers.init_dense(ko, cfg.num_heads * cfg.head_dim, cfg.d_model, dtype),
    }
    if cfg.use_qk_norm and not cross:
        p["q_norm_scale"] = jnp.zeros((cfg.head_dim,), dtype)
        p["k_norm_scale"] = jnp.zeros((cfg.head_dim,), dtype)
    return p


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> KVCache:
    shape = (batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def _apply_rope(x, positions, ctx: Ctx):
    cfg = ctx.cfg
    if cfg.use_mrope:
        return layers.mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return layers.rope(x, positions, cfg.rope_theta)


def _allow(q_pos, k_pos, *, causal: bool, window: Optional[int]):
    """(B, Sq, Sk) boolean allow-mask from position ids."""
    m = k_pos[:, None, :] >= 0  # -1 marks unwritten cache slots
    if causal:
        m &= q_pos[:, :, None] >= k_pos[:, None, :]
    if window is not None:
        m &= q_pos[:, :, None] - k_pos[:, None, :] < window
    return m


def _scale_cap(s, softcap, scale):
    s = s * scale
    return jnp.tanh(s / softcap) * softcap if softcap else s


def _scores(q, k, softcap, scale):
    # q: (B, Sq, H, hd), k: (B, Sk, H, hd) -> (B, H, Sq, Sk)
    with jax.named_scope("scores"):
        s = jnp.einsum("bqhd,bthd->bhqt", q.astype(jnp.float32), k.astype(jnp.float32))
        return _scale_cap(s, softcap, scale)


def _attend_direct(q, k, v, q_pos, k_pos, *, causal, window, softcap, scale):
    logits = _scores(q, k, softcap, scale)
    allow = _allow(q_pos, k_pos, causal=causal, window=window)
    logits = jnp.where(allow[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqt,bthd->bqhd", probs, v.astype(jnp.float32))


def _slot_positions(t: int, last: jax.Array, offset: jax.Array) -> jax.Array:
    """(B, T) key position of each cache slot: slot j of row i holds
    position ``j - offset[i]``; slots outside ``[offset[i], last[i]]``
    (left pads, the unwritten tail, an admission hole) are -1."""
    jj = jnp.arange(t, dtype=jnp.int32)[None, :]
    ok = (jj >= offset[:, None]) & (jj <= last[:, None])
    return jnp.where(ok, jj - offset[:, None], -1)


def _attend_decode(q, cache, k_new, v_new, q_pos, k_pos, k_pos_new, *,
                   window, softcap, scale):
    """One query per row over the cache as stored, plus its own key.

    q (B, 1, H, hd); ``cache`` leaves (B, T, KV, hd) with the write slot
    already masked out of ``k_pos`` (B, T); k_new/v_new (B, 1, KV, hd) in
    the cache dtype; k_pos_new (B,).  The query is grouped to (B, KV, G,
    hd) and contracted against the unrepeated cache with float32
    accumulation — equal to a float32 product of the two operands, since
    a product of two bf16 values is exact in float32.  Probabilities stay
    float32 into the product with V (``Precision.HIGHEST``).
    """
    b, _, h, hd = q.shape
    kvh = cache.k.shape[2]
    f32 = jnp.float32
    qg = q.reshape(b, kvh, h // kvh, hd)
    with jax.named_scope("scores"):
        s_cache = _scale_cap(jnp.einsum("bkgd,btkd->bkgt", qg, cache.k,
                                        preferred_element_type=f32), softcap, scale)
        s_new = _scale_cap(jnp.einsum("bkgd,bkd->bkg", qg, k_new[:, 0],
                                      preferred_element_type=f32), softcap, scale)
    allow = _allow(q_pos, k_pos, causal=True, window=window)  # (B, 1, T)
    allow_new = _allow(q_pos, k_pos_new[:, None], causal=True, window=window)
    s_cache = jnp.where(allow[:, None], s_cache, NEG_INF)
    s_new = jnp.where(allow_new[:, None], s_new[..., None], NEG_INF)
    # softmax over the T cache scores and the new token's score together
    m = jnp.maximum(s_cache.max(axis=-1, keepdims=True), s_new)
    e_cache, e_new = jnp.exp(s_cache - m), jnp.exp(s_new - m)
    denom = e_cache.sum(axis=-1, keepdims=True) + e_new
    p_cache, p_new = e_cache / denom, e_new / denom
    out = jnp.einsum("bkgt,btkd->bkgd", p_cache, cache.v,
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=f32)
    out = out + p_new * v_new[:, 0, :, None, :].astype(f32)
    return out.reshape(b, 1, h, hd)


def _attend_flash(q, k, v, q_pos, k_pos, *, causal, window, softcap, scale,
                  q_chunk=Q_CHUNK, k_chunk=K_CHUNK):
    """Blockwise attention; q (B,S,H,hd), k/v (B,T,H,hd)."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    q_chunk = min(q_chunk, s)
    k_chunk = min(k_chunk, t)
    assert s % q_chunk == 0 and t % k_chunk == 0, (s, t, q_chunk, k_chunk)
    nq, nk = s // q_chunk, t // k_chunk

    kc = jnp.moveaxis(k.reshape(b, nk, k_chunk, h, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nk, k_chunk, h, hd), 1, 0)
    kpc = jnp.moveaxis(k_pos.reshape(b, nk, k_chunk), 1, 0)

    def q_block(args):
        qb, qpb = args  # (B, qc, H, hd), (B, qc)

        def k_step(carry, xs):
            m, l, acc = carry
            kb, vb, kpb = xs
            logits = _scores(qb, kb, softcap, scale)  # (B,H,qc,kc)
            allow = _allow(qpb, kpb, causal=causal, window=window)
            logits = jnp.where(allow[:, None, :, :], logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqt,bthd->bhqd", p, vb.astype(jnp.float32)
            )
            return (m_new, l, acc), None

        m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(k_step, (m0, l0, a0), (kc, vc, kpc))
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,H,qc,hd)
        return jnp.moveaxis(out, 1, 2)  # (B,qc,H,hd)

    qb = jnp.moveaxis(q.reshape(b, nq, q_chunk, h, hd), 1, 0)
    qpb = jnp.moveaxis(q_pos.reshape(b, nq, q_chunk), 1, 0)
    out = jax.lax.map(q_block, (qb, qpb))  # (nq, B, qc, H, hd)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)


def attention(
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    ctx: Ctx,
    *,
    local: bool = False,
    causal: bool = True,
    cache: Optional[KVCache] = None,
    cache_pos: Optional[jax.Array] = None,
    kv_x: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
) -> tuple[jax.Array, Optional[KVCache]]:
    """General attention.

    Self-attention: ``kv_x`` is None.  Cross-attention: ``kv_x`` is the
    encoder memory (not causal, no rope).  Decode: ``cache`` given,
    x is (B, 1, D) and ``cache_pos`` the int32 cache write offset —
    either a scalar (legacy: physical slot == position for every row) or
    a per-row ``(B,)`` vector.  With a vector, ``positions`` carries each
    row's *true* position ids and the per-slot key positions are derived
    from the row's pad offset ``cache_pos + S - 1 - positions[:, -1]``:
    slot j of row i holds true position ``j - offset_i`` and slots outside
    ``[offset_i, cache_pos_i + S - 1]`` (left pads, unwritten tail, the
    admission hole of a retired-and-refilled slot) are masked invalid.
    This is what lets left-padded prompts decode at their true positions
    and lets the continuous-batching scheduler keep rows at different
    depths of one physical cache.

    Returns ``(out, cache)``.  With S > 1 the cache comes back written.
    A decode step (S == 1) leaves ``cache`` untouched and returns only the
    new token's entries, (B, 1, KV, hd) in the cache dtype: the caller
    writes them at ``cache_pos`` with :func:`write_kv`.
    """
    cfg = ctx.cfg
    b, s, _ = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kvh
    mpos = positions if not cfg.use_mrope else positions[0]  # masks use t-ids

    x = layers.scale(x, cfg.attn_in_multiplier)
    q = layers.dense(x, params["wq"], ctx, "attn").reshape(b, s, h, hd)
    src = x if kv_x is None else kv_x
    k = layers.dense(src, params["wk"], ctx, "attn").reshape(b, src.shape[1], kvh, hd)
    k = layers.scale(k, cfg.key_multiplier)
    v = layers.dense(src, params["wv"], ctx, "attn").reshape(b, src.shape[1], kvh, hd)

    if cfg.use_qk_norm and "q_norm_scale" in params:
        q = layers.rms_norm(q, params["q_norm_scale"], cfg.norm_eps)
        k = layers.rms_norm(k, params["k_norm_scale"], cfg.norm_eps)
    if kv_x is None:
        q = _apply_rope(q, positions, ctx)
        k = _apply_rope(k, positions if kv_positions is None else kv_positions, ctx)
    q = constrain(q, DP, None, TP, None)

    causal_ = causal and kv_x is None
    window = cfg.local_window if local else None
    scale = hd**-0.5
    softcap = cfg.attn_logit_softcap
    q_pos = mpos
    if cache is None or kv_x is not None:
        new_cache = None
        k_pos = mpos if kv_positions is None else kv_positions
    else:
        # physical slot of the newest token, and each row's left-pad
        # offset (physical - true position)
        per_row = getattr(cache_pos, "ndim", 0) >= 1
        last = jnp.asarray(cache_pos, jnp.int32) + jnp.int32(s - 1)
        if per_row:
            offset = last - mpos[:, -1]
        else:
            last = jnp.broadcast_to(last, (b,))
            offset = jnp.zeros((b,), jnp.int32)
        if s == 1:
            # decode: read the cache in place, write slot masked out; the
            # caller writes the new entries (write_kv)
            new_cache = KVCache(k.astype(cache.k.dtype), v.astype(cache.v.dtype))
            old = KVCache(constrain(cache.k, DP, TP, None, None),
                          constrain(cache.v, DP, TP, None, None))
            out = _attend_decode(
                q, old, new_cache.k, new_cache.v, q_pos,
                _slot_positions(cache.k.shape[1], last - 1, offset), last - offset,
                window=window, softcap=softcap, scale=scale,
            )
            out = constrain(out.reshape(b, s, h * hd).astype(x.dtype), DP, None, TP)
            return _out_proj(out, params, ctx), new_cache
        with jax.named_scope("cache_update"):
            if per_row:
                starts = jnp.asarray(cache_pos, jnp.int32)
                kfull = _row_update(cache.k, k.astype(cache.k.dtype), starts)
                vfull = _row_update(cache.v, v.astype(cache.v.dtype), starts)
            else:
                kfull = jax.lax.dynamic_update_slice(
                    cache.k, k.astype(cache.k.dtype), (0, cache_pos, 0, 0)
                )
                vfull = jax.lax.dynamic_update_slice(
                    cache.v, v.astype(cache.v.dtype), (0, cache_pos, 0, 0)
                )
        new_cache = KVCache(kfull, vfull)
        k, v = kfull, vfull
        k_pos = _slot_positions(kfull.shape[1], last, offset)

    ap_attn = cfg.approx.for_target("attn") if (
        cfg.approx.enabled and "attn" in cfg.approx.targets
    ) else None
    fused_approx = (
        ap_attn is not None
        and ap_attn.mode in ("bitexact", "lowrank")
        and ap_attn.backend != "reference"
        and cfg.attn_impl == "pallas"
    )

    if cfg.attn_impl == "pallas":
        # VMEM-resident flash kernel; k/v stay unrepeated (GQA head
        # mapping happens in the BlockSpec index_map, not in HBM)
        from repro.kernels.flash_attention import flash_attention
        from repro.kernels.ops import use_interpret

        k = constrain(k, DP, None, None, None)
        v = constrain(v, DP, None, None, None)

        def _block(dim: int) -> int:  # largest power-of-two divisor <= 512
            b_ = 512
            while b_ > 1 and dim % b_:
                b_ //= 2
            return b_

        if fused_approx:
            # quality-tier attention: the QK/AV contractions themselves
            # run through the approximate multiplier inside the online-
            # softmax tile loop (kernels/approx_attention.py) — the
            # projections above already went through the engine.
            from repro.kernels.approx_attention import (
                approx_flash_attention, attn_tiles, validate_attn_mode,
            )

            validate_attn_mode(ap_attn.mode, ap_attn.n)
            bq_d, bk_d = attn_tiles(ap_attn.mode)
            out = approx_flash_attention(
                q, k, v, q_pos, k_pos, ap_attn.mode, ap_attn.n, ap_attn.t,
                ap_attn.fix_to_1, ap_attn.rank, causal_, window, softcap,
                scale, min(_block(q.shape[1]), bq_d),
                min(_block(k.shape[1]), bk_d), use_interpret(),
            )
        else:
            out = flash_attention(
                q, k, v, q_pos, k_pos, causal_, window, softcap, scale,
                _block(q.shape[1]), _block(k.shape[1]), use_interpret(),
            )
    else:
        # GQA: repeat kv to the flat head axis (cache stays unrepeated)
        if g > 1:
            with jax.named_scope("gqa_repeat"):
                k = jnp.repeat(k, g, axis=2)
                v = jnp.repeat(v, g, axis=2)
        k = constrain(k, DP, None, TP, None)
        v = constrain(v, DP, None, TP, None)
        if s > Q_CHUNK or k.shape[1] > 4 * K_CHUNK:
            out = _attend_flash(
                q, k, v, q_pos, k_pos, causal=causal_, window=window, softcap=softcap, scale=scale
            )
        else:
            out = _attend_direct(
                q, k, v, q_pos, k_pos, causal=causal_, window=window, softcap=softcap, scale=scale
            )
    out = out.reshape(b, s, h * hd).astype(x.dtype)
    out = constrain(out, DP, None, TP)
    return _out_proj(out, params, ctx), new_cache


def _out_proj(out, params, ctx: Ctx):
    return layers.scale(layers.dense(out, params["wo"], ctx, "attn"),
                        ctx.cfg.attn_out_multiplier)
