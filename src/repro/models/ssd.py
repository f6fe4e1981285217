"""Mamba-2 SSD (state-space duality) mixer, chunked matmul formulation.

The selective state-space recurrence

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * B_t x_t^T        (per head)
    y_t = C_t . S_t + D * x_t

is evaluated in the SSD "chunked" form (Dao & Gu, 2024): the sequence is
split into chunks of length L; within a chunk the output is an
attention-like quadratic matmul against a decay-masked Gram matrix
(MXU-friendly), and across chunks a *linear* recurrence over O(S/L)
chunk states is evaluated with a log-depth associative scan — which is
what makes the 500k-token shapes tractable.

B and C come in ``ssm_groups`` groups, and head h reads group
``h // (H / G)`` (the heads of a group are contiguous).  With
``ssm_norm`` the gated output y * silu(z) passes an RMSNorm over each
group before ``out_proj`` (``FalconH1RMSNormGated`` with
``norm_before_gate`` false, as Mamba-2 and Falcon-H1 publish it).  The
muP multipliers of the config (on the input, on each of in_proj's z / x /
B / C / dt parts, on the output) are applied only where they differ
from 1.

Decode carries (conv state, SSM state (B, H, P, N)) and is O(1) per token.
A prefill given position ids treats positions < 0 as left pads: their conv
input, dt and post-conv x, B and C are zero, so a pad decays nothing and
injects nothing, and the conv and SSM state a padded prompt leaves are
those of the unpadded prompt.
In/out projections route through the approximate multiplier; the state
update stays exact (the accumulator, per DESIGN.md §Arch-applicability).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import DP, TP, constrain
from repro.models import layers
from repro.models.layers import Ctx

__all__ = ["SSDCache", "init_ssd", "ssd_block", "init_ssd_cache"]


class SSDCache(NamedTuple):
    conv: jax.Array  # (B, conv_width - 1, d_conv_channels)
    state: jax.Array  # (B, H, P, N) f32


def _dims(cfg: ModelConfig):
    d_inner = cfg.d_inner or 2 * cfg.d_model
    heads = cfg.ssm_heads or d_inner // cfg.ssm_head_dim
    return d_inner, heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups


def init_ssd(key, cfg: ModelConfig, dtype) -> dict:
    d = cfg.d_model
    d_inner, h, p, n, g = _dims(cfg)
    conv_ch = d_inner + 2 * g * n  # x, B, C all pass the causal conv
    ks = jax.random.split(key, 4)
    in_dim = 2 * d_inner + 2 * g * n + h  # z, x, B, C, dt
    params = {
        "in_proj": layers.init_dense(ks[0], d, in_dim, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.conv_width, conv_ch), jnp.float32) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "ssm_a": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),  # A = -exp(.)
        "ssm_d": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((h,), 0.01))).astype(jnp.float32),
        "out_proj": layers.init_dense(ks[3], d_inner, d, dtype),
    }
    if cfg.ssm_norm:
        params["norm"] = jnp.zeros((d_inner,), dtype)
    return params


def init_ssd_cache(cfg: ModelConfig, batch: int, dtype) -> SSDCache:
    d_inner, h, p, n, g = _dims(cfg)
    return SSDCache(
        conv=jnp.zeros((batch, cfg.conv_width - 1, d_inner + 2 * g * n), dtype),
        state=jnp.zeros((batch, h, p, n), jnp.float32),
    )


def _segsum(z: jax.Array) -> jax.Array:
    """(..., L) -> (..., L, L) lower-tri cumulative sums: out[i,j] = sum_{j<k<=i} z_k."""
    l = z.shape[-1]
    cs = jnp.cumsum(z, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def _ssd_chunked(xh, dt, a, b_in, c_in, chunk: int):
    """Chunked SSD.

    xh: (B, S, H, P); dt: (B, S, H) (post-softplus); a: (H,) (negative);
    b_in, c_in: (B, S, G, N), head h reading group h // (H / G).
    Returns (y (B, S, H, P), final state (B, H, P, N)).  Heads are
    handled as (G, H / G) so that no group is repeated to its heads.
    """
    bsz, s, h, p = xh.shape
    g, n = b_in.shape[-2:]
    k = h // g
    l = min(chunk, s)
    pad = (-s) % l
    if pad:
        # zero-pad the tail: dt=0 makes padded steps identity on the state
        # (decay exp(0)=1, zero injection), so the final state is exact.
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_in = jnp.pad(c_in, ((0, 0), (0, pad), (0, 0), (0, 0)))
    s_pad = s + pad
    nc = s_pad // l

    xc = xh.reshape(bsz, nc, l, g, k, p)
    dtc = dt.reshape(bsz, nc, l, g, k)
    bc = b_in.reshape(bsz, nc, l, g, n)
    cc = c_in.reshape(bsz, nc, l, g, n)

    da = dtc * a.reshape(g, k)  # (B, C, L, G, K) log-decay increments
    da_cum = jnp.cumsum(da, axis=2)  # within-chunk cumulative
    da_total = da_cum[:, :, -1]  # (B, C, G, K)

    # ---- intra-chunk (quadratic, MXU): Y[i] = sum_{j<=i} C_i.B_j exp(seg) dt_j x_j
    seg = _segsum(jnp.moveaxis(da, 2, -1))  # (B, C, G, K, L, L)
    gram = jnp.einsum("bcign,bcjgn->bcgij", cc, bc)  # (B, C, G, L, L)
    m = gram[:, :, :, None] * jnp.exp(seg)  # (B, C, G, K, L, L)
    y_intra = jnp.einsum("bcgkij,bcjgk,bcjgkp->bcigkp", m, dtc, xc)

    # ---- chunk states: S_c = sum_j exp(da_total - da_cum_j) dt_j B_j x_j^T
    decay_state = jnp.exp(da_total[:, :, None] - da_cum)  # (B, C, L, G, K)
    states = jnp.einsum("bclgn,bclgk,bclgkp->bcgkpn", bc, decay_state * dtc, xc)

    # ---- inter-chunk linear recurrence over C (associative scan, log depth)
    decay_chunk = jnp.exp(da_total)  # (B, C, G, K)

    def comb(left, right):
        al, sl = left
        ar, sr = right
        return al * ar, sl * ar[..., None, None] + sr

    a_all, s_all = jax.lax.associative_scan(comb, (decay_chunk, states), axis=1)
    # state entering chunk c is s_all[c-1]
    s_prev = jnp.concatenate(
        [jnp.zeros_like(s_all[:, :1]), s_all[:, :-1]], axis=1
    )  # (B, C, G, K, P, N)

    # ---- inter-chunk output: y_off[i] = C_i . (exp(da_cum_i) S_prev)
    decay_out = jnp.exp(da_cum)  # (B, C, L, G, K)
    y_inter = jnp.einsum("bclgn,bcgkpn,bclgk->bclgkp", cc, s_prev, decay_out)

    y = (y_intra + y_inter).reshape(bsz, s_pad, h, p)[:, :s]
    return y, s_all[:, -1].reshape(bsz, h, p, n)  # final state (B, H, P, N)


def _ssd_step(xh, dt, a, b_in, c_in, state):
    """One token over the carried state: S = exp(dt a) S + dt B x^T;
    y = C.S.  xh (B, H, P), dt (B, H), b_in / c_in (B, G, N), state
    (B, H, P, N); returns (y (B, H, P), state)."""
    bsz, h, p = xh.shape
    g, n = b_in.shape[-2:]
    k = h // g
    dtg = dt.reshape(bsz, g, k)
    da = jnp.exp(dtg * a.reshape(g, k))  # (B, G, K)
    dbx = jnp.einsum("bgk,bgn,bgkp->bgkpn", dtg, b_in, xh.reshape(bsz, g, k, p))
    state = da[..., None, None] * state.reshape(bsz, g, k, p, n) + dbx
    y = jnp.einsum("bgn,bgkpn->bgkp", c_in, state)
    return y.reshape(bsz, h, p), state.reshape(bsz, h, p, n)


def _gated_norm(y, z, scale, groups: int, eps: float):
    """The gated RMSNorm of Mamba-2 and Falcon-H1, in float32: the gate
    first, y * silu(z), then RMSNorm over each of ``groups`` contiguous
    chunks of the channels, weight ``1 + scale``."""
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    yg = y.reshape(*y.shape[:-1], groups, y.shape[-1] // groups)
    yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), axis=-1, keepdims=True) + eps)
    return yg.reshape(y.shape) * (1.0 + scale.astype(jnp.float32))


def ssd_block(
    params: dict,
    x: jax.Array,
    ctx: Ctx,
    cache: Optional[SSDCache] = None,
    positions: Optional[jax.Array] = None,
) -> tuple[jax.Array, Optional[SSDCache]]:
    """x: (B, S, d_model) -> (out, new_cache).  ``positions`` (B, S), when
    given for a prefill, marks left pads with ids < 0 (module docstring)."""
    cfg = ctx.cfg
    d_inner, h, p, n, g = _dims(cfg)
    bsz, s, _ = x.shape
    mult = cfg.ssm_multipliers
    f32 = jnp.float32
    # pads, (B, S, 1); decode steps and unpadded forwards have none
    valid = (positions >= 0)[..., None] if (
        positions is not None and positions.ndim == 2 and s > 1) else None

    def unpadded(v):
        return v if valid is None else jnp.where(valid, v, jnp.zeros((), v.dtype))

    with jax.named_scope("ssm"):
        with jax.named_scope("in_proj"):
            zxbcdt = layers.dense(layers.scale(x, cfg.ssm_in_multiplier),
                                  params["in_proj"], ctx, "mlp")
            z, xr, b_in, c_in, dt = jnp.split(
                zxbcdt, [d_inner, 2 * d_inner, 2 * d_inner + g * n, 2 * d_inner + 2 * g * n],
                axis=-1,
            )
            z = layers.scale(z, mult[0])
            conv_in = jnp.concatenate([layers.scale(xr, mult[1]), layers.scale(b_in, mult[2]),
                                       layers.scale(c_in, mult[3])], axis=-1)
            dt = layers.scale(dt.astype(f32), mult[4])

        # causal depthwise conv (shared with rglru implementation style)
        from repro.models.rglru import _causal_conv

        with jax.named_scope("conv"):
            conv_cache = cache.conv if cache is not None else None
            conv_out, new_conv = _causal_conv(unpadded(conv_in), params["conv_w"],
                                              params["conv_b"], conv_cache)
            conv_out = unpadded(jax.nn.silu(conv_out))
            xr, b_in, c_in = jnp.split(conv_out, [d_inner, d_inner + g * n], axis=-1)

        dt = unpadded(jax.nn.softplus(dt + params["dt_bias"]))  # (B, S, H)
        a = -jnp.exp(params["ssm_a"])  # (H,)
        xh = xr.astype(f32).reshape(bsz, s, h, p)
        xh = constrain(xh, DP, None, TP, None)
        b_in = b_in.astype(f32).reshape(bsz, s, g, n)
        c_in = c_in.astype(f32).reshape(bsz, s, g, n)

        if cache is not None and s == 1:
            with jax.named_scope("step"):
                y, state = _ssd_step(xh[:, 0], dt[:, 0], a, b_in[:, 0], c_in[:, 0],
                                     cache.state)
                y = y[:, None]
        else:
            # prefill: a provided cache is assumed fresh (zero state) — the
            # chunked scan starts from S_0 = 0 and the final state is returned.
            with jax.named_scope("scan"):
                y, state = _ssd_chunked(xh, dt, a, b_in, c_in, cfg.ssm_chunk)

        y = y + params["ssm_d"][None, None, :, None] * xh
        y = y.reshape(bsz, s, d_inner)
        with jax.named_scope("norm"):
            if cfg.ssm_norm:
                y = _gated_norm(y, z, params["norm"], g, cfg.norm_eps).astype(x.dtype)
            else:
                y = y.astype(x.dtype) * jax.nn.silu(z)
        y = constrain(y, DP, None, TP)
        with jax.named_scope("out_proj"):
            out = layers.scale(layers.dense(y, params["out_proj"], ctx, "mlp"),
                               cfg.ssm_out_multiplier)
    new_cache = SSDCache(new_conv, state) if cache is not None else None
    return out, new_cache
