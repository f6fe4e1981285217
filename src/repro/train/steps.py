"""Train / serve step factories.

``make_train_step`` builds the jitted step: gradient-accumulation
microbatching (``lax.scan`` over microbatches with running grad mean),
optional int8 error-feedback gradient compression, AdamW (f32 or 8-bit
states), vocab-chunked CE, MoE aux loss.  Remat is already applied inside
the model's scanned layer groups per ``cfg.remat``.

``make_prefill_step`` / ``make_decode_step`` are the serving pair:
prefill writes the KV/recurrent caches at positions [0, S); decode
consumes one token at ``pos`` with the cache as carried state.  These are
exactly what the dry-run lowers for the ``prefill_*`` / ``decode_*`` /
``long_*`` shape cells.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, TrainConfig
from repro.models.registry import Model
from repro.optim import adamw, compress
from repro.train.losses import chunked_cross_entropy

__all__ = [
    "TrainState",
    "init_train_state",
    "make_train_step",
    "make_prefill_step",
    "make_decode_step",
    "loss_fn",
]

AUX_COEF = 0.01


class TrainState(NamedTuple):
    params: Any
    opt: adamw.OptState
    comp: Optional[compress.CompressState]
    rng: jax.Array
    step: jax.Array


def init_train_state(model: Model, tcfg: TrainConfig, key: jax.Array) -> TrainState:
    kp, kr = jax.random.split(key)
    params = model.init_params(kp)
    return TrainState(
        params=params,
        opt=adamw.init(params, tcfg),
        comp=compress.init_state(params) if tcfg.grad_compress_bits else None,
        rng=kr,
        step=jnp.zeros((), jnp.int32),
    )


def _positions(cfg: ModelConfig, batch: dict) -> jax.Array:
    tokens = batch["tokens"]
    b, s = tokens.shape
    pos = jnp.arange(s, dtype=jnp.int32)[None, :] * jnp.ones((b, 1), jnp.int32)
    if cfg.use_mrope:
        return jnp.broadcast_to(pos[None], (3, b, s))  # text-only stream: t=h=w
    return pos


def _head_matrix(params: dict, cfg: ModelConfig) -> jax.Array:
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def loss_fn(params, batch: dict, rng, model: Model) -> tuple[jax.Array, dict]:
    cfg = model.cfg
    ctx = model.ctx(rng)
    kwargs = {}
    if cfg.is_encdec:
        kwargs["src_embeds"] = batch["src_embeds"]
        se = batch["src_embeds"]
        kwargs["src_pos"] = jnp.arange(se.shape[1], dtype=jnp.int32)[None, :] * jnp.ones(
            (se.shape[0], 1), jnp.int32
        )
    elif cfg.frontend and "embeds" in batch:
        kwargs["embeds"] = batch["embeds"]
    hidden, _, aux = model.forward(
        params, batch.get("tokens"), _positions(cfg, batch), ctx, **kwargs
    )
    if cfg.lm_head_multiplier != 1.0:  # lm_head's (h W) m, as (h m) W
        hidden = hidden.astype(jnp.float32) * cfg.lm_head_multiplier
    ce = chunked_cross_entropy(
        hidden, _head_matrix(params, cfg), batch["labels"], softcap=cfg.final_logit_softcap
    )
    loss = ce + AUX_COEF * aux
    return loss, {"loss": ce, "aux": aux}


def make_train_step(model: Model, tcfg: TrainConfig):
    """Returns ``train_step(state, batch) -> (state, metrics)`` (jit-ready)."""
    cfg = model.cfg
    accum = max(1, tcfg.grad_accum)

    def step_fn(state: TrainState, batch: dict):
        rng = jax.random.fold_in(state.rng, state.step)
        grad_of = jax.value_and_grad(loss_fn, has_aux=True)

        if accum == 1:
            (loss, parts), grads = grad_of(state.params, batch, rng, model)
        else:
            def micro(b):
                return jax.tree_util.tree_map(
                    lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]), b
                )

            mb = micro(batch)

            def body(carry, xb):
                g_acc, l_acc = carry
                (l, _), g = grad_of(state.params, xb, rng, model)
                g_acc = jax.tree_util.tree_map(lambda a, b_: a + b_, g_acc, g)
                return (g_acc, l_acc + l), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            (grads, loss), _ = jax.lax.scan(body, (g0, jnp.float32(0.0)), mb)
            grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
            loss = loss / accum
            parts = {"loss": loss, "aux": jnp.float32(0.0)}

        new_comp = state.comp
        cmetrics: dict = {}
        if state.comp is not None:
            grads, new_comp, cmetrics = compress.compress_grads(grads, state.comp)

        new_params, new_opt, ometrics = adamw.update(grads, state.opt, state.params, tcfg)
        metrics = {"loss": loss, **parts, **ometrics, **cmetrics}
        new_state = TrainState(
            params=new_params,
            opt=new_opt,
            comp=new_comp,
            rng=state.rng,
            step=state.step + 1,
        )
        return new_state, metrics

    return step_fn


# ------------------------------------------------------------------ serving
def make_prefill_step(model: Model, max_seq: int, *, mem_len: int = 0):
    """prefill(params, batch) -> (caches, last_token_logits).

    ``batch["positions"]`` (optional, (B, S) int32) supplies per-row
    *true* position ids for left-padded prompts — pad slots carry
    negative ids and are masked out of the KV cache, so a short prompt
    padded to the bucket width attends (and is later attended to) at its
    real positions.  Without it, positions are the shared ``arange(S)``
    (every row full-length, the legacy static-batch behavior).
    """
    cfg = model.cfg
    cache_dtype = jnp.dtype(cfg.dtype)

    def prefill(params, batch: dict):
        tokens = batch["tokens"]
        b, s = tokens.shape
        ctx = model.ctx()
        caches = model.init_caches(b, max_seq, cache_dtype, mem_len=mem_len)
        if cfg.is_encdec:
            memory = model.encode(params, batch["src_embeds"], batch["src_pos"], ctx)
            ck, cv = model.precompute_cross(params, memory, ctx)
            caches = caches._replace(cross_k=ck.astype(cache_dtype), cross_v=cv.astype(cache_dtype))
        if "positions" in batch:
            pos = jnp.asarray(batch["positions"], jnp.int32)
            cache_pos = jnp.zeros((b,), jnp.int32)  # per-row path in attention
        else:
            pos = jnp.arange(s, dtype=jnp.int32)[None, :] * jnp.ones((b, 1), jnp.int32)
            cache_pos = jnp.int32(0)
        if cfg.use_mrope:
            pos = jnp.broadcast_to(pos[None], (3, b, s))
        with jax.named_scope("forward"):
            hidden, caches, _ = model.forward(
                params, tokens, pos, ctx, caches=caches, cache_pos=cache_pos
            )
        with jax.named_scope("lm_head"):
            logits = model.lm_head(params, hidden[:, -1:, :])
        return caches, logits

    return prefill


def make_decode_step(model: Model):
    """decode(params, caches, token (B,1), pos, write_pos=None) -> (logits, caches).

    ``pos`` is either a scalar (legacy: every row decodes at the same
    position, which doubles as the cache write slot) or a per-row ``(B,)``
    vector of *true* positions.  With a vector, ``write_pos`` (``(B,)``,
    default ``pos``) gives each row's physical cache write slot — for a
    row admitted into a continuous-batching slot with pad offset d, the
    true position p writes physical slot p + d.  Per-row positions are
    what let one decode step advance rows sitting at different depths.
    """
    cfg = model.cfg

    def decode(params, caches, token: jax.Array, pos: jax.Array, write_pos=None):
        b = token.shape[0]
        ctx = model.ctx()
        pos = jnp.asarray(pos, jnp.int32)
        if pos.ndim == 0:
            p = pos * jnp.ones((b, 1), jnp.int32)
            cache_pos = pos
        else:
            p = pos[:, None]
            cache_pos = pos if write_pos is None else jnp.asarray(write_pos, jnp.int32)
        if cfg.use_mrope:
            p = jnp.broadcast_to(p[None], (3, b, 1))
        with jax.named_scope("forward"):
            hidden, new_caches, _ = model.forward(
                params, token, p, ctx, caches=caches, cache_pos=cache_pos
            )
        with jax.named_scope("lm_head"):
            logits = model.lm_head(params, hidden)
        return logits, new_caches

    return decode
