"""Seeded random weights, made by the benchmark for the program and the
reference alike.

Each weight is ``(u - 127.5) * 2**-e`` with ``u`` a random byte: every
value is exact in bfloat16, so the served weights and the reference's
float32 copy hold the same numbers, however either side computes them.
Each tensor of layer ``l`` has its own key, ``fold_in(fold_in(base, leaf),
l)``, so the reference can make one layer at a time while the program's
stacked tree is made in one jitted call on the device.

Norm weights are ``1 + delta`` in the published convention; the program
stores ``delta`` (it computes ``x * (1 + scale)``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# A leaf is named here, or given by the integer id a family's own table
# assigns it, from ``FAMILY_LEAF_BASE`` up, so that no two leaves share
# their bytes.
_LEAF = {"embed": 0, "head": 1, "final_norm": 2, "ln1": 3, "ln2": 4, "q": 5,
         "k": 6, "v": 7, "o": 8, "q_norm": 9, "k_norm": 10, "gate": 11,
         "up": 12, "down": 13}
FAMILY_LEAF_BASE = 100
_BYTE_STD = math.sqrt((256 ** 2 - 1) / 12.0)  # std of u - 127.5
_NORM_EXP = 9  # norm deltas within +-0.25


def base_key(seed: int) -> jax.Array:
    """A threefry key from any whole-number seed, however large."""
    data = np.random.SeedSequence([seed % 2 ** 64, 0x5EED]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(data, jnp.uint32))


def _exp(fan_in: int) -> int:
    """Power-of-two scale giving a std near ``fan_in ** -0.5``."""
    return int(round(math.log2(_BYTE_STD * math.sqrt(fan_in))))


def _leaf_id(leaf: str | int) -> int:
    if isinstance(leaf, str):
        return _LEAF[leaf]
    if leaf < FAMILY_LEAF_BASE:
        raise ValueError(f"a family's leaf ids start at {FAMILY_LEAF_BASE}, got {leaf}")
    return leaf


def _bytes(key, leaf: str | int, layer: int, shape) -> jax.Array:
    k = jax.random.fold_in(jax.random.fold_in(key, _leaf_id(leaf)), layer)
    return jax.random.bits(k, shape, jnp.uint8).astype(jnp.float32) - 127.5


def matrix(key, leaf: str | int, layer: int, shape, fan_in: int) -> jax.Array:
    return (_bytes(key, leaf, layer, shape) * 2.0 ** -_exp(fan_in)).astype(jnp.bfloat16)


def norm_delta(key, leaf: str | int, layer: int, shape) -> jax.Array:
    return (_bytes(key, leaf, layer, shape) * 2.0 ** -_NORM_EXP).astype(jnp.bfloat16)


def layer_weights(cfg: dict, key, layer: int) -> dict:
    """One decoder layer in bfloat16, by the benchmark's own names; norms
    as deltas from 1.  ``layer`` may be traced (vmapped)."""
    d, h, kv, hd, f = (cfg["hidden_size"], cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"],
                       cfg["intermediate_size"])
    w = {
        "ln1": norm_delta(key, "ln1", layer, (d,)),
        "ln2": norm_delta(key, "ln2", layer, (d,)),
        "q": matrix(key, "q", layer, (d, h * hd), d),
        "k": matrix(key, "k", layer, (d, kv * hd), d),
        "v": matrix(key, "v", layer, (d, kv * hd), d),
        "o": matrix(key, "o", layer, (h * hd, d), h * hd),
        "gate": matrix(key, "gate", layer, (d, f), d),
        "up": matrix(key, "up", layer, (d, f), d),
        "down": matrix(key, "down", layer, (f, d), f),
    }
    if cfg["qk_norm"]:
        w["q_norm"] = norm_delta(key, "q_norm", layer, (hd,))
        w["k_norm"] = norm_delta(key, "k_norm", layer, (hd,))
    return w


def embedding(cfg: dict, key) -> jax.Array:
    d = cfg["hidden_size"]
    return matrix(key, "embed", 0, (cfg["vocab_size"], d), d)


def head(cfg: dict, key) -> jax.Array:
    """Untied output head (d, vocab); tied configurations use the embedding."""
    d = cfg["hidden_size"]
    return matrix(key, "head", 0, (d, cfg["vocab_size"]), d)


def final_norm(cfg: dict, key) -> jax.Array:
    return norm_delta(key, "final_norm", 0, (cfg["hidden_size"],))


# The program's parameter tree (repro.models.transformer) by path, mapped
# to the names above.  A leaf not listed here means the program's layout
# changed, and the benchmark refuses to guess.
_PROGRAM_LAYER = {
    ("ln1",): "ln1", ("ln2",): "ln2",
    ("attn", "wq"): "q", ("attn", "wk"): "k", ("attn", "wv"): "v", ("attn", "wo"): "o",
    ("attn", "q_norm_scale"): "q_norm", ("attn", "k_norm_scale"): "k_norm",
    ("ffn", "w1"): "gate", ("ffn", "w3"): "up", ("ffn", "w2"): "down",
}


def _path_names(path) -> tuple:
    return tuple(getattr(p, "key", getattr(p, "name", p)) for p in path)


def _checked(names: tuple, out: jax.Array, sds) -> jax.Array:
    """``out`` as the program's leaf ``names``, if it has the shape and
    dtype that the program's ``init_params`` gives that leaf (``sds``)."""
    if out.shape != sds.shape or out.dtype != sds.dtype:
        raise ValueError(f"parameter {names}: benchmark makes {out.shape} {out.dtype}, "
                         f"program wants {sds.shape} {sds.dtype}")
    return out


def program_params(cfg: dict, model, seed: int):
    """The program's parameter tree for ``model``, made on the device in one
    jitted call from ``seed``; every leaf is checked against the shape and
    dtype the program's own ``init_params`` gives it."""
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    layers = cfg["num_hidden_layers"]

    def make(key):
        stacked = jax.vmap(lambda i: layer_weights(cfg, key, i))(jnp.arange(layers))
        top = {("embed",): embedding(cfg, key), ("final_norm",): final_norm(cfg, key)}
        if not cfg["tie_word_embeddings"]:
            top[("lm_head",)] = head(cfg, key)

        def leaf(path, sds):
            names = _path_names(path)
            if names in top:
                out = top[names]
            elif names[:2] == ("scan", "sub0") and names[2:] in _PROGRAM_LAYER:
                out = stacked[_PROGRAM_LAYER[names[2:]]]
            else:
                raise ValueError(f"unexpected program parameter {names}")
            return _checked(names, out, sds)

        return jax.tree_util.tree_map_with_path(leaf, shapes)

    return jax.jit(make)(base_key(seed))
