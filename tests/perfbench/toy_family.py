"""A family for tests, copied into a test checkout as
``perfbench/families/toy.py``: the dense decoder's mathematics over layer
weights drawn under leaf ids of its own, as the family of a new
architecture draws its leaves.  The embedding, the head and the final norm
are the shared ones, by name; the config check and the operation count
are the dense family's."""

from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import reference, weights

_LEAF = {name: weights.FAMILY_LEAF_BASE + i for i, name in enumerate(
    ("ln1", "ln2", "q", "k", "v", "o", "q_norm", "k_norm", "gate", "up", "down"))}

_dense_spec = importlib.util.spec_from_file_location(
    "perfbench_family_toy_dense", Path(__file__).with_name("dense.py"))
dense = importlib.util.module_from_spec(_dense_spec)
_dense_spec.loader.exec_module(dense)

program_config = dense.program_config
request_ops = dense.request_ops


def layer_weights(cfg: dict, key, layer) -> dict:
    d, h, kv, hd, f = (cfg["hidden_size"], cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"],
                       cfg["intermediate_size"])
    mats = {"q": (d, h * hd), "k": (d, kv * hd), "v": (d, kv * hd), "o": (h * hd, d),
            "gate": (d, f), "up": (d, f), "down": (f, d)}
    norms = {"ln1": d, "ln2": d, **({"q_norm": hd, "k_norm": hd} if cfg["qk_norm"] else {})}
    w = {n: weights.matrix(key, _LEAF[n], layer, s, s[0]) for n, s in mats.items()}
    w.update({n: weights.norm_delta(key, _LEAF[n], layer, (s,)) for n, s in norms.items()})
    return w


def program_params(cfg: dict, model, seed: int):
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))

    def make(key):
        stacked = jax.vmap(lambda i: layer_weights(cfg, key, i))(
            jnp.arange(cfg["num_hidden_layers"]))
        top = {("embed",): weights.embedding(cfg, key),
               ("final_norm",): weights.final_norm(cfg, key)}
        if not cfg["tie_word_embeddings"]:
            top[("lm_head",)] = weights.head(cfg, key)

        def leaf(path, sds):
            names = weights._path_names(path)
            out = top[names] if names in top else stacked[weights._PROGRAM_LAYER[names[2:]]]
            return weights._checked(names, out, sds)

        return jax.tree_util.tree_map_with_path(leaf, shapes)

    return jax.jit(make)(weights.base_key(seed))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(cfg_items, control, key, layer, hs):
    cfg = dict(cfg_items)
    w = reference._f32_layer(layer_weights(cfg, key, layer))
    ref = jax.lax.map(lambda h: reference._block(cfg, reference._mm_f32, h, w), hs[0])
    if not control:
        return (ref,)
    return ref, jax.lax.map(lambda h: reference._block(cfg, reference._mm_int8, h, w), hs[1])


def gaps(cfg: dict, seed: int, prompts, served, length: int, *, control=False):
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items() if not isinstance(v, (dict, list))))
    key = weights.base_key(seed)
    tokens = np.zeros((len(prompts), length), np.int32)
    seq, pos, target = [], [], []
    for i, (p, s) in enumerate(zip(prompts, served)):
        full = np.concatenate([np.asarray(p, np.int32), np.asarray(s, np.int32)])[:-1]
        tokens[i, : len(full)] = full
        seq += [i] * len(s)
        pos += list(range(len(p) - 1, len(p) - 1 + len(s)))
        target += [int(t) for t in s]
    h = reference._embed(cfg_items, key, jnp.asarray(tokens))
    hs = (h, h) if control else (h,)
    for layer in range(cfg["num_hidden_layers"]):
        hs = _layer(cfg_items, control, key, jnp.int32(layer), hs)
    out = reference._head_gaps(cfg_items, control, key, hs, jnp.asarray(seq, jnp.int32),
                               jnp.asarray(pos, jnp.int32), jnp.asarray(target, jnp.int32))
    out = [np.asarray(o, np.float64) for o in out]
    return {"served": out[0], "control": out[1] if control else None}
