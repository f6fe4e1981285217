"""Shared neural layers: norms, RoPE (+M-RoPE), dense (with the paper's
approximate-multiplier modes), gated MLPs.

All layers are pure functions over nested-dict parameter trees; no flax.
``Ctx`` threads trace-time context (approx config, PRNG for error
injection, decode position) through the stack without global state.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ApproxConfig, ModelConfig
from repro.distributed.sharding import DP, TP, constrain
from repro.engine import dispatch as _engine, modes as _engine_modes

__all__ = ["Ctx", "rms_norm", "rope", "mrope", "dense", "mlp", "scale", "init_dense",
           "init_mlp"]


@dataclasses.dataclass
class Ctx:
    """Trace-time call context (not a pytree; holds config + rng plumbing)."""

    cfg: ModelConfig
    rng: Optional[jax.Array] = None  # base key for error injection
    _counter: int = 0  # python-level unique id per dense call site
    aux_losses: list = dataclasses.field(default_factory=list)  # MoE balance terms

    def next_key(self) -> Optional[jax.Array]:
        self._counter += 1
        if self.rng is None:
            return None
        return jax.random.fold_in(self.rng, self._counter)

    def aux_loss(self) -> jax.Array:
        if not self.aux_losses:
            return jnp.float32(0.0)
        total = self.aux_losses[0]
        for a in self.aux_losses[1:]:
            total = total + a
        return total


# --------------------------------------------------------------------- init
def _normal(key, shape, dtype, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_dense(key, d_in: int, d_out: int, dtype) -> jax.Array:
    return _normal(key, (d_in, d_out), dtype, d_in**-0.5)


def init_mlp(key, cfg: ModelConfig, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": init_dense(k1, cfg.d_model, cfg.d_ff, dtype),
        "w3": init_dense(k3, cfg.d_model, cfg.d_ff, dtype),
        "w2": init_dense(k2, cfg.d_ff, cfg.d_model, dtype),
    }


# ------------------------------------------------------------------- layers
def scale(x: jax.Array, m: float) -> jax.Array:
    """``x * m`` rounded once to ``x``'s dtype (a muP multiplier), or ``x``
    itself where ``m`` is 1: the check is in Python, so a model without
    multipliers traces no multiply."""
    if m == 1.0:
        return x
    return (x.astype(jnp.float32) * m).astype(x.dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def _rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    freqs = _rope_freqs(x.shape[-1], theta)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def mrope(x: jax.Array, positions: jax.Array, theta: float, sections: tuple) -> jax.Array:
    """Qwen2-VL multimodal RoPE.  positions: (3, B, S) — t/h/w ids.

    The head_dim/2 frequency bands are partitioned into ``sections``;
    each band rotates with its own position stream.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = _rope_freqs(x.shape[-1], theta)  # (half,)
    sec_id = jnp.repeat(jnp.arange(len(sections)), jnp.array(sections), total_repeat_length=half)
    pos = positions.astype(jnp.float32)  # (3, B, S)
    # ang[b, s, f] = pos[sec_id[f], b, s] * freqs[f]
    ang = jnp.take(pos, sec_id, axis=0)  # (half, B, S)
    ang = jnp.moveaxis(ang, 0, -1) * freqs  # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------------------- approximate dense
def _approx_2d(x2: jax.Array, w: jax.Array, ap: ApproxConfig, key) -> jax.Array:
    """One engine call: the mode registry owns fakequant/inject/bitexact/
    lowrank semantics (including the straight-through gradient rule that
    used to be re-implemented here)."""
    return _engine.matmul(
        x2.astype(jnp.float32),
        w.astype(jnp.float32),
        n=ap.n,
        t=ap.t,
        fix_to_1=ap.fix_to_1,
        mode=ap.mode,
        rank=ap.rank,
        key=_engine_modes.resolve_key(ap.mode, key),
        backend=ap.backend,
    )


def dense(x: jax.Array, w: jax.Array, ctx: Ctx, kind: str = "mlp") -> jax.Array:
    """x: (..., d_in) @ w (d_in, d_out), optionally through the approximate
    multiplier (paper technique) when ``kind`` is targeted.  The effective
    (n, t, mode, backend) comes from ``approx.for_target(kind)``, so a
    quality tier's per-GEMM-class selections (engine.config) apply here
    without the call site knowing about tiers."""
    ap = ctx.cfg.approx
    if not ap.enabled or kind not in ap.targets:
        return jnp.dot(x, w.astype(x.dtype))
    ap = ap.for_target(kind)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    out = _approx_2d(x2, w, ap, ctx.next_key())
    return out.reshape(*lead, w.shape[-1]).astype(x.dtype)


def mlp(params: dict, x: jax.Array, ctx: Ctx) -> jax.Array:
    """Gated MLP: SwiGLU (silu) or GeGLU (gelu)."""
    act = jax.nn.silu if ctx.cfg.ffn_activation == "silu" else (
        lambda v: jax.nn.gelu(v, approximate=True)
    )
    gate_m, down_m = ctx.cfg.mlp_multipliers
    h = act(scale(dense(x, params["w1"], ctx, "mlp"), gate_m)) * dense(x, params["w3"], ctx, "mlp")
    h = constrain(h, DP, None, TP)
    return scale(dense(h, params["w2"], ctx, "mlp"), down_m)
