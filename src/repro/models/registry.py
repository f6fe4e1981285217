"""Model registry: one uniform handle over decoder-only and enc-dec stacks.

``build_model(cfg)`` returns a ``Model`` whose methods close over the
config and dispatch by family.  All higher layers (train steps, serving,
dry-run) go through this interface only.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, transformer
from repro.models.layers import Ctx

__all__ = ["Model", "build_model"]


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------- params
    def init_params(self, key: jax.Array) -> dict:
        if self.cfg.is_encdec:
            return encdec.init_params(key, self.cfg)
        return transformer.init_params(key, self.cfg)

    def ctx(self, rng: Optional[jax.Array] = None) -> Ctx:
        return Ctx(cfg=self.cfg, rng=rng)

    # ------------------------------------------------------------ forward
    def forward(
        self,
        params: dict,
        tokens: Optional[jax.Array],
        positions: jax.Array,
        ctx: Ctx,
        *,
        embeds: Optional[jax.Array] = None,
        src_embeds: Optional[jax.Array] = None,
        src_pos: Optional[jax.Array] = None,
        caches: Any = None,
        cache_pos=None,
    ) -> tuple[jax.Array, Any, jax.Array]:
        """Returns (hidden (B, S, D), new_caches, aux_loss)."""
        if self.cfg.is_encdec:
            if caches is None:
                memory = encdec.encode(params, src_embeds, src_pos, ctx)
                hidden, _ = encdec.decode_forward(
                    params, tokens, positions, src_pos, ctx, memory=memory
                )
                return hidden, None, jnp.float32(0.0)
            mem_len = caches.cross_k.shape[2]
            mem_pos = jnp.arange(mem_len, dtype=jnp.int32)[None, :] * jnp.ones(
                (tokens.shape[0], 1), jnp.int32
            )
            hidden, new_caches = encdec.decode_forward(
                params, tokens, positions, mem_pos, ctx,
                caches=caches, cache_pos=cache_pos,
            )
            return hidden, new_caches, jnp.float32(0.0)
        return transformer.forward(
            params, tokens, positions, ctx,
            embeds=embeds, caches=caches, cache_pos=cache_pos,
        )

    def lm_head(self, params: dict, hidden: jax.Array) -> jax.Array:
        return transformer.lm_head(params, hidden, self.cfg)

    # ------------------------------------------------------------- caches
    def init_caches(self, batch: int, max_seq: int, dtype, *, mem_len: int = 0):
        if self.cfg.is_encdec:
            return encdec.init_dec_caches(self.cfg, batch, max_seq, mem_len, dtype)
        return transformer.init_caches(self.cfg, batch, max_seq, dtype)

    def state_bytes(self, caches) -> int:
        """Bytes of recurrent state (conv + SSM / RG-LRU) in ``caches``."""
        return 0 if self.cfg.is_encdec else transformer.state_bytes(caches)

    # ------------------------------------------------- enc-dec extras
    def encode(self, params, src_embeds, src_pos, ctx):
        assert self.cfg.is_encdec
        return encdec.encode(params, src_embeds, src_pos, ctx)

    def precompute_cross(self, params, memory, ctx):
        assert self.cfg.is_encdec
        return encdec.precompute_cross(params, memory, ctx)

    def param_count(self, params: dict) -> int:
        return sum(p.size for p in jax.tree_util.tree_leaves(params))


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg=cfg)
