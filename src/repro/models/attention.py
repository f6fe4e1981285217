"""Multi-head attention: GQA/MQA, RoPE/M-RoPE, qk-norm, logit softcaps,
sliding-window (local) masking, and a KV cache for prefill + decode.

Tensor-parallel layout: attention runs on a *flat* head axis H = KV * G
(k/v are repeated from KV to H at use — the cache stays unrepeated), so a
single ``model``-axis constraint shards the whole computation whenever H
divides the axis (true for 8/10 assigned archs at model=16; qwen2-vl H=28
and recurrentgemma H=10 replicate and are flagged in EXPERIMENTS.md).

Prefill / training uses a blockwise online-softmax (flash-style)
formulation: an outer ``lax.map`` over query chunks and an inner
``lax.scan`` over key chunks carrying (running max, denominator,
accumulator) — peak live logits are (B, H, q_chunk, k_chunk) instead of
(B, H, S, T).

Decode (s == 1) takes the direct path with the KV cache *sequence* axis
sharded over the model axis (flash-decode style): per-device partial
logits over T/|model| keys, with the softmax max/sum reductions lowering
to all-reduces — this is what makes decode_32k × batch 128 fit.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import DP, TP, ambient_mesh, constrain
from repro.models import layers
from repro.models.layers import Ctx

__all__ = ["KVCache", "init_attn", "attention", "init_kv_cache"]

NEG_INF = -2.3819763e38  # bf16-safe large negative
Q_CHUNK = 1024
K_CHUNK = 1024


def _no_mesh() -> bool:
    return ambient_mesh() is None


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


def _scores(q, k, softcap, scale):
    # q: (B, Sq, H, hd), k: (B, Sk, H, hd) -> (B, H, Sq, Sk)
    with jax.named_scope("scores"):
        s = jnp.einsum("bqhd,bthd->bhqt", q.astype(jnp.float32), k.astype(jnp.float32))
        s *= scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        return s


def _attend_direct(q, k, v, q_pos, k_pos, *, causal, window, softcap, scale):
    logits = _scores(q, k, softcap, scale)
    allow = _allow(q_pos, k_pos, causal=causal, window=window)
    logits = jnp.where(allow[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqt,bthd->bqhd", probs, v.astype(jnp.float32))


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
    """
    cfg = ctx.cfg
    b, s, _ = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kvh
    mpos = positions if not cfg.use_mrope else positions[0]  # masks use t-ids

    q = layers.dense(x, params["wq"], ctx, "attn").reshape(b, s, h, hd)
    src = x if kv_x is None else kv_x
    k = layers.dense(src, params["wk"], ctx, "attn").reshape(b, src.shape[1], kvh, hd)
    v = layers.dense(src, params["wv"], ctx, "attn").reshape(b, src.shape[1], kvh, hd)

    if cfg.use_qk_norm and "q_norm_scale" in params:
        q = layers.rms_norm(q, params["q_norm_scale"], cfg.norm_eps)
        k = layers.rms_norm(k, params["k_norm_scale"], cfg.norm_eps)
    if kv_x is None:
        q = _apply_rope(q, positions, ctx)
        k = _apply_rope(k, positions if kv_positions is None else kv_positions, ctx)
    q = constrain(q, DP, None, TP, None)

    decode = s == 1 and cache is not None
    per_row = cache_pos is not None and getattr(cache_pos, "ndim", 0) >= 1
    if cache is not None and kv_x is None:
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
        if decode:  # flash-decode: shard the cache sequence axis over TP
            kfull = constrain(kfull, DP, TP, None, None)
            vfull = constrain(vfull, DP, TP, None, None)
        new_cache = KVCache(kfull, vfull)
        k, v = kfull, vfull
        t = kfull.shape[1]
        jj = jnp.arange(t, dtype=jnp.int32)[None, :] * jnp.ones((b, 1), jnp.int32)
        if per_row:
            last = starts + jnp.int32(s - 1)  # (B,) physical slot of newest token
            offset = last - mpos[:, -1]  # physical - true == per-row left-pad
            k_pos = jnp.where(
                (jj >= offset[:, None]) & (jj <= last[:, None]),
                jj - offset[:, None],
                -1,
            )
        else:
            k_pos = jnp.where(jj <= cache_pos + s - 1, jj, -1)
        q_pos = mpos
    else:
        new_cache = None
        k_pos = mpos if kv_positions is None else kv_positions
        q_pos = mpos

    causal_ = causal and kv_x is None
    window = cfg.local_window if local else None
    scale = hd**-0.5
    softcap = cfg.attn_logit_softcap

    ap_attn = cfg.approx.for_target("attn") if (
        cfg.approx.enabled and "attn" in cfg.approx.targets
    ) else None
    fused_approx = (
        ap_attn is not None
        and ap_attn.mode in ("bitexact", "lowrank")
        and ap_attn.backend != "reference"
        and cfg.attn_impl == "pallas"
        and not decode
    )

    if not decode and cfg.attn_impl == "pallas":
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
    elif decode and cfg.attn_impl == "pallas" and _no_mesh():
        # single-device serving: stream the KV cache through VMEM
        # (multi-device decode keeps the XLA path — the cache is
        # sequence-sharded over the model axis there)
        from repro.kernels.flash_attention import flash_decode
        from repro.kernels.ops import use_interpret

        out = flash_decode(
            q[:, 0], k, v, mpos[:, -1], k_pos,
            window=window, softcap=softcap, scale=scale,
            interpret=use_interpret(),
        )[:, None]
    else:
        # GQA: repeat kv to the flat head axis (cache stays unrepeated)
        if g > 1:
            with jax.named_scope("gqa_repeat"):
                k = jnp.repeat(k, g, axis=2)
                v = jnp.repeat(v, g, axis=2)
        if not decode:
            k = constrain(k, DP, None, TP, None)
            v = constrain(v, DP, None, TP, None)
        if not decode and (s > Q_CHUNK or k.shape[1] > 4 * K_CHUNK):
            out = _attend_flash(
                q, k, v, q_pos, k_pos, causal=causal_, window=window, softcap=softcap, scale=scale
            )
        else:
            out = _attend_direct(
                q, k, v, q_pos, k_pos, causal=causal_, window=window, softcap=softcap, scale=scale
            )
    out = out.reshape(b, s, h * hd).astype(x.dtype)
    out = constrain(out, DP, None, TP)
    return layers.dense(out, params["wo"], ctx, "attn"), new_cache
