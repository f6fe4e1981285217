"""The Falcon-H1 family: every block runs a Mamba-2 mixer and grouped-query
attention side by side on one normed input and adds them, then a SwiGLU
feed-forward; muP multipliers throughout.

The reference follows ``modeling_falcon_h1.py`` of ``transformers``
(``FalconH1DecoderLayer.forward``, ``FalconH1Mixer.torch_forward``,
``FalconH1RMSNormGated``, ``compute_mup_vector``), equation for equation,
in plain ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``:

    x = rms(h, ln1)
    h = h + ssm_out_m * mamba(x) + attn_out_m * attn(attn_in_m * x)
    h = h + down_m * down(up(x2) * silu(gate_m * gate(x2))),  x2 = rms(h, ln2)

``mamba`` scales its input by ``ssm_in_multiplier`` and in_proj's z, x, B,
C and dt parts by the five ``ssm_multipliers``; runs a depthwise causal
conv with bias and silu over (x, B, C); and then the selective state
space as its **sequential recurrence** over tokens, per head h of group g
(the program evaluates it in the chunked form, so the two share no
algorithm):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,   y_t = S_t C_t + D x_t

with dt_t = softplus(dt + dt_bias), A = -exp(A_log); then y * silu(z),
an RMSNorm over each of the ``mamba_n_groups`` channel groups with its
weight, and out_proj.  Attention scales its keys by ``key_multiplier``,
applies rotate-half RoPE, and takes a full causal softmax at
``head_dim ** -0.5``.  The embedding is scaled by
``embedding_multiplier`` and the logits by ``lm_head_multiplier``.  It
runs layer by layer, each layer's weights made from the seed, with no
cache and one sequence at a time, and the head in vocabulary blocks.

The int8 control is the dense family's: every weight product in int8
(per-output-channel weight scales, per-token activation scales, int32
sums); the recurrence stays in float32.

Weights (``program_params``) are drawn with ``perfbench.weights`` under
this family's leaf ids.  ``A_log``, ``dt_bias`` and ``D`` are drawn from
the same bytes into the published initial ranges: A uniform in
[1, mamba_n_heads] (``A = arange(1, n_heads + 1)`` at init), dt
log-uniform in [0.001, 0.1] (``time_step_min``, ``time_step_max``) with
``dt_bias`` its inverse softplus, and D within 1 +- 0.25 (ones at init).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights
from perfbench.reference import HIGHEST, _mm_f32, _mm_int8, _rms, _rope

# The published keys a configuration file states, and the field of the
# program's ModelConfig each must equal.
_SIZES = {
    "hidden_size": "d_model", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "torch_dtype": "dtype",
    "mamba_d_ssm": "d_inner", "mamba_n_heads": "ssm_heads", "mamba_d_head": "ssm_head_dim",
    "mamba_d_state": "ssm_state", "mamba_n_groups": "ssm_groups",
    "mamba_d_conv": "conv_width", "mamba_chunk_size": "ssm_chunk",
    "mamba_rms_norm": "ssm_norm",
    "embedding_multiplier": "embed_multiplier", "lm_head_multiplier": "lm_head_multiplier",
    "key_multiplier": "key_multiplier", "attention_in_multiplier": "attn_in_multiplier",
    "attention_out_multiplier": "attn_out_multiplier",
    "ssm_in_multiplier": "ssm_in_multiplier", "ssm_out_multiplier": "ssm_out_multiplier",
    "ssm_multipliers": "ssm_multipliers", "mlp_multipliers": "mlp_multipliers",
}
# Published keys that select what the reference implements; a file that
# states anything else describes another model.
_FIXED = {"model_type": "falcon_h1", "hidden_act": "silu", "attention_bias": False,
          "mamba_conv_bias": True, "mamba_proj_bias": False, "projectors_bias": False,
          "mlp_bias": False, "mamba_use_mlp": True, "rope_scaling": None,
          "attn_layer_indices": None, "mamba_rms_norm": True,
          "mamba_norm_before_gate": False, "tie_word_embeddings": False}
# What the reference implements of the program's ModelConfig.
_PLAIN = {"layer_pattern": ("attn_ssd",), "ffn_activation": "silu", "use_qk_norm": False,
          "use_post_norm": False, "embed_scale": False, "num_experts": 0,
          "final_logit_softcap": None, "attn_logit_softcap": None, "use_mrope": False,
          "encoder_layers": 0, "scan_layers": True}

_LEAF = {name: weights.FAMILY_LEAF_BASE + i for i, name in enumerate((
    "embed", "head", "final_norm", "ln1", "ln2", "q", "k", "v", "o", "gate", "up", "down",
    "in_proj", "conv_w", "conv_b", "a_log", "dt_bias", "d_skip", "ssm_norm", "out_proj"))}
# the program's per-layer parameter tree (repro.models.transformer,
# scan/sub0) by path, mapped to the names above
_PROGRAM_LAYER = {
    ("ln1",): "ln1", ("ln2",): "ln2",
    ("attn", "wq"): "q", ("attn", "wk"): "k", ("attn", "wv"): "v", ("attn", "wo"): "o",
    ("ffn", "w1"): "gate", ("ffn", "w3"): "up", ("ffn", "w2"): "down",
    ("ssd", "in_proj"): "in_proj", ("ssd", "conv_w"): "conv_w", ("ssd", "conv_b"): "conv_b",
    ("ssd", "ssm_a"): "a_log", ("ssd", "dt_bias"): "dt_bias", ("ssd", "ssm_d"): "d_skip",
    ("ssd", "norm"): "ssm_norm", ("ssd", "out_proj"): "out_proj",
}
_DT_RANGE = (0.001, 0.1)  # time_step_min, time_step_max
_HEAD_BLOCK = 8192  # most vocabulary rows of the head in one product


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file, checked against
    every size and multiplier the file states."""
    from repro.configs.registry import get_config

    for key, want in _FIXED.items():
        if cfg[key] != want:
            raise ValueError(f"{cfg['name']}: {key}={cfg[key]!r}, the reference "
                             f"implements {want!r}")
    prog = get_config(cfg["program"]["registry"], **cfg["program"].get("overrides", {}))
    for key, field in _SIZES.items():
        have, want = getattr(prog, field), cfg[key]
        if isinstance(want, list):
            want = tuple(want)
        if have != want:
            raise ValueError(f"{cfg['name']}: program {field}={have!r} "
                             f"but the configuration states {key}={cfg[key]!r}")
    for field, want in _PLAIN.items():
        if getattr(prog, field) != want:
            raise ValueError(f"{cfg['name']}: program {field}={getattr(prog, field)!r}, "
                             f"the reference implements {want!r}")
    return prog


def _sizes(cfg: dict) -> dict:
    d, e, g, n = (cfg["hidden_size"], cfg["mamba_d_ssm"], cfg["mamba_n_groups"],
                  cfg["mamba_d_state"])
    return dict(d=d, e=e, g=g, n=n, h=cfg["num_attention_heads"],
                kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                f=cfg["intermediate_size"], mh=cfg["mamba_n_heads"],
                mp=cfg["mamba_d_head"], kc=cfg["mamba_d_conv"], conv=e + 2 * g * n,
                proj=2 * e + 2 * g * n + cfg["mamba_n_heads"])


def _unit(key, leaf: str, layer, shape) -> jax.Array:
    """Seeded values in (0, 1) in steps of 1/256, from the bytes of
    ``weights.norm_delta`` (deltas (u - 127.5) / 512 of a byte u)."""
    delta = weights.norm_delta(key, _LEAF[leaf], layer, shape).astype(jnp.float32)
    return (delta * 512.0 + 128.0) / 256.0


def layer_weights(cfg: dict, key, layer) -> dict:
    """One layer by the family's names: matrices and the conv in bfloat16,
    norms as deltas from 1 (bfloat16), A_log, dt_bias and D in float32.
    ``layer`` may be traced (vmapped)."""
    s = _sizes(cfg)
    d, e, f = s["d"], s["e"], s["f"]
    mats = {"q": (d, s["h"] * s["hd"]), "k": (d, s["kv"] * s["hd"]),
            "v": (d, s["kv"] * s["hd"]), "o": (s["h"] * s["hd"], d),
            "gate": (d, f), "up": (d, f), "down": (f, d),
            "in_proj": (d, s["proj"]), "out_proj": (e, d), "conv_w": (s["kc"], s["conv"])}
    w = {name: weights.matrix(key, _LEAF[name], layer, shape, shape[0])
         for name, shape in mats.items()}
    for name, size in (("ln1", d), ("ln2", d), ("ssm_norm", e), ("conv_b", s["conv"])):
        w[name] = weights.norm_delta(key, _LEAF[name], layer, (size,))
    mh = s["mh"]
    lo, hi = (math.log(v) for v in _DT_RANGE)
    dt = jnp.exp(lo + _unit(key, "dt_bias", layer, (mh,)) * (hi - lo))
    w["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))  # softplus(dt_bias) == dt
    w["a_log"] = jnp.log(1.0 + _unit(key, "a_log", layer, (mh,)) * (mh - 1))
    w["d_skip"] = 1.0 + weights.norm_delta(key, _LEAF["d_skip"], layer, (mh,)).astype(jnp.float32)
    return w


def _top(cfg: dict, key) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": weights.matrix(key, _LEAF["embed"], 0, (v, d), d),
            "head": weights.matrix(key, _LEAF["head"], 0, (d, v), d),
            "final_norm": weights.norm_delta(key, _LEAF["final_norm"], 0, (d,))}


def program_params(cfg: dict, model, seed: int):
    """The program's parameter tree for ``model``, made on the device in one
    jitted call from ``seed``; every leaf is checked against the shape and
    dtype the program's own ``init_params`` gives it."""
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))

    def make(key):
        stacked = jax.vmap(lambda i: layer_weights(cfg, key, i))(
            jnp.arange(cfg["num_hidden_layers"]))
        t = _top(cfg, key)
        top = {("embed",): t["embed"], ("lm_head",): t["head"],
               ("final_norm",): t["final_norm"]}

        def leaf(path, sds):
            names = weights._path_names(path)
            if names in top:
                out = top[names]
            elif names[:2] == ("scan", "sub0") and names[2:] in _PROGRAM_LAYER:
                out = stacked[_PROGRAM_LAYER[names[2:]]]
            else:
                raise ValueError(f"unexpected program parameter {names}")
            return weights._checked(names, out, sds)

        return jax.tree_util.tree_map_with_path(leaf, shapes)

    return jax.jit(make)(weights.base_key(seed))


# ------------------------------------------------------------- reference
def _f32_layer(w: dict) -> dict:
    out = {name: a.astype(jnp.float32) for name, a in w.items()}
    for name in ("ln1", "ln2", "ssm_norm"):
        out[name] = 1.0 + out[name]
    return out


def _mamba(cfg, s, mm, x, w):
    """FalconH1Mixer on one sequence x (T, D), the SSM as its recurrence."""
    t = x.shape[0]
    e, g, n, mh, mp, kc = s["e"], s["g"], s["n"], s["mh"], s["mp"], s["kc"]
    z_m, x_m, b_m, c_m, dt_m = cfg["ssm_multipliers"]
    proj = mm(x * cfg["ssm_in_multiplier"], w["in_proj"])
    z, xbc, dt = jnp.split(proj, [e, e + s["conv"]], axis=-1)
    z, dt = z * z_m, dt * dt_m
    xbc = xbc * jnp.concatenate([jnp.full((e,), x_m), jnp.full((g * n,), b_m),
                                 jnp.full((g * n,), c_m)])
    # depthwise causal conv: out[t] = sum_k w[k] * in[t - (kc - 1) + k] + b
    padded = jnp.concatenate([jnp.zeros((kc - 1, s["conv"])), xbc])
    conv = sum(padded[k:k + t] * w["conv_w"][k] for k in range(kc)) + w["conv_b"]
    xs, bs, cs = jnp.split(jax.nn.silu(conv), [e, e + g * n], axis=-1)
    xs = xs.reshape(t, mh, mp)
    group = jnp.arange(mh) // (mh // g)  # head -> its B/C group
    bs = bs.reshape(t, g, n)[:, group]  # (T, heads, N)
    cs = cs.reshape(t, g, n)[:, group]
    dt = jax.nn.softplus(dt + w["dt_bias"])  # (T, heads)
    a = -jnp.exp(w["a_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + dt_t[:, None, None] * x_t[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t, precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((mh, mp, n)), (xs, bs, cs, dt))
    y = (y + w["d_skip"][:, None] * xs).reshape(t, e)
    y = (y * jax.nn.silu(z)).reshape(t, g, e // g)  # gate, then norm per group
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    y = y.reshape(t, e) * w["ssm_norm"]
    return mm(y, w["out_proj"]) * cfg["ssm_out_multiplier"]


def _attention(cfg, s, mm, x, w):
    """FalconH1Attention on one sequence x (T, D): full causal softmax."""
    t = x.shape[0]
    nh, nkv, hd = s["h"], s["kv"], s["hd"]
    pos = jnp.arange(t)
    q = mm(x, w["q"]).reshape(t, nh, hd)
    k = mm(x, w["k"]).reshape(t, nkv, hd) * cfg["key_multiplier"]
    v = mm(x, w["v"]).reshape(t, nkv, hd)
    theta = float(cfg["rope_theta"])  # the file states it as the integer 10**11
    q = _rope(q, pos, theta)
    k = _rope(k, pos, theta)
    k = jnp.repeat(k, nh // nkv, axis=1)  # query head i reads kv head i // (nh / nkv)
    v = jnp.repeat(v, nh // nkv, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
    sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(t, nh * hd)
    return mm(a, w["o"])


def _block(cfg, mm, h, w):
    """FalconH1DecoderLayer on one sequence h (T, D); w in float32."""
    s = _sizes(cfg)
    eps = cfg["rms_norm_eps"]
    x = _rms(h, w["ln1"], eps)
    h = (h + _mamba(cfg, s, mm, x, w)
         + _attention(cfg, s, mm, x * cfg["attention_in_multiplier"], w)
         * cfg["attention_out_multiplier"])
    x = _rms(h, w["ln2"], eps)
    gate_m, down_m = cfg["mlp_multipliers"]
    return h + mm(mm(x, w["up"]) * jax.nn.silu(mm(x, w["gate"]) * gate_m), w["down"]) * down_m


def _items(cfg: dict) -> tuple:
    """The configuration as a hashable static argument (lists as tuples)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items() if not isinstance(v, dict)))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(cfg_items, control, key, layer, hs):
    """Layer ``layer`` over every sequence, for the reference stream and,
    with ``control``, the int8 stream."""
    cfg = dict(cfg_items)
    w = _f32_layer(layer_weights(cfg, key, layer))
    ref = jax.lax.map(lambda h: _block(cfg, _mm_f32, h, w), hs[0])
    if not control:
        return (ref,)
    return ref, jax.lax.map(lambda h: _block(cfg, _mm_int8, h, w), hs[1])


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(cfg_items, key, tokens):
    cfg = dict(cfg_items)
    rows = jnp.take(_top(cfg, key)["embed"], tokens, axis=0).astype(jnp.float32)
    return rows * cfg["embedding_multiplier"]


def _head_block(vocab: int) -> int:
    """The largest divisor of ``vocab`` up to ``_HEAD_BLOCK``."""
    return max(b for b in range(1, min(vocab, _HEAD_BLOCK) + 1) if vocab % b == 0)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head_gaps(cfg_items, control, key, hs, seq, pos, target):
    """Gap below the reference's best logit of each target token and, with
    ``control``, of the token the int8 stream puts first; the head's
    product runs one block of vocabulary rows at a time."""
    cfg = dict(cfg_items)
    top = _top(cfg, key)
    eps, mult, vocab = cfg["rms_norm_eps"], cfg["lm_head_multiplier"], cfg["vocab_size"]
    norm = 1.0 + top["final_norm"].astype(jnp.float32)
    x_ref = _rms(hs[0][seq, pos], norm, eps)
    x_ctl = _rms(hs[1][seq, pos], norm, eps) if control else None
    blk = _head_block(vocab)
    rows = x_ref.shape[0]
    neg = jnp.full((rows,), -jnp.inf)

    def one(carry, start):
        best, at_target, ctl_best, ctl_ref = carry
        wh = jax.lax.dynamic_slice_in_dim(top["head"], start, blk, axis=1).astype(jnp.float32)
        ref = _mm_f32(x_ref, wh) * mult
        best = jnp.maximum(best, ref.max(axis=-1))
        inside = (target >= start) & (target < start + blk)
        idx = jnp.clip(target - start, 0, blk - 1)
        here = jnp.take_along_axis(ref, idx[:, None], axis=-1)[:, 0]
        at_target = jnp.where(inside, here, at_target)
        if control:
            ctl = _mm_int8(x_ctl, wh) * mult
            j = jnp.argmax(ctl, axis=-1)
            v = jnp.take_along_axis(ctl, j[:, None], axis=-1)[:, 0]
            better = v > ctl_best  # the first of equal maxima, as argmax takes it
            ctl_ref = jnp.where(better, jnp.take_along_axis(ref, j[:, None], axis=-1)[:, 0],
                                ctl_ref)
            ctl_best = jnp.where(better, v, ctl_best)
        return (best, at_target, ctl_best, ctl_ref), None

    (best, at_target, _, ctl_ref), _ = jax.lax.scan(
        one, (neg, neg, neg, neg), jnp.arange(0, vocab, blk))
    return (best - at_target,) + ((best - ctl_ref,) if control else ())


def gaps(cfg: dict, seed: int, prompts, served, length: int, *, control=False):
    """Per served token, the gap by which its reference logit lies below the
    reference's best (and, with ``control``, the same gap of the int8
    stream's first choice at that position).

    ``prompts[i]`` and ``served[i]`` are one request; the reference runs
    over ``prompt + served[:-1]``, right-padded to ``length`` so that one
    compiled program serves every run of a cell (a right pad follows every
    token it could change).  Returns ``{"served": array, "control": array
    | None}``.
    """
    items = _items(cfg)
    key = weights.base_key(seed)
    tokens = np.zeros((len(prompts), length), np.int32)
    seq, pos, target = [], [], []
    for i, (p, s) in enumerate(zip(prompts, served)):
        full = np.concatenate([np.asarray(p, np.int32), np.asarray(s, np.int32)])[:-1]
        if len(full) > length:
            raise ValueError(f"sequence of {len(full)} tokens exceeds {length}")
        tokens[i, : len(full)] = full
        seq += [i] * len(s)
        pos += list(range(len(p) - 1, len(p) - 1 + len(s)))
        target += [int(t) for t in s]
    with jax.default_matmul_precision("highest"):
        h = _embed(items, key, jnp.asarray(tokens))
        hs = (h, h) if control else (h,)
        for layer in range(cfg["num_hidden_layers"]):
            hs = _layer(items, control, key, jnp.int32(layer), hs)
        out = _head_gaps(items, control, key, hs, jnp.asarray(seq, jnp.int32),
                         jnp.asarray(pos, jnp.int32), jnp.asarray(target, jnp.int32))
    out = [np.asarray(o, np.float64) for o in out]
    return {"served": out[0], "control": out[1] if control else None}


# ------------------------------------------------------------- counting
def _layer_params(cfg: dict) -> dict:
    """Parameters of one layer by kind: multiplied matrices, the conv, and
    vectors (norms, conv bias; A_log, dt_bias and D in float32)."""
    s = _sizes(cfg)
    d = s["d"]
    matmul = (d * s["h"] * s["hd"] * 2 + 2 * d * s["kv"] * s["hd"]  # q, o; k, v
              + 3 * d * s["f"]  # gate, up, down
              + d * s["proj"] + s["e"] * d)  # in_proj, out_proj
    return {"matmul": matmul, "conv": s["kc"] * s["conv"],
            "vectors": 2 * d + s["e"] + s["conv"], "f32": 3 * s["mh"]}


def request_ops(cfg: dict, prompt_len: int, tokens_out: int) -> float:
    """Model operations of one served request: every prompt token and
    every output token but the last goes through the layers (two per
    weight of each product, attention over its causal context, the conv,
    and the state update and readout of every head); each output token
    comes from one pass through the head.  Padding is not counted."""
    s = _sizes(cfg)
    layers = cfg["num_hidden_layers"]
    forwarded = prompt_len + tokens_out - 1
    contexts = forwarded * (forwarded + 1) // 2  # keys seen: 1 + 2 + ... + forwarded
    attn_per_key = 4 * s["h"] * s["hd"]  # QK^T and PV
    # per token and head: S = exp(dt A) S + dt x B^T (3 per state element),
    # y = S C (2 per element), D x (2 per channel)
    ssm = s["mh"] * (5 * s["mp"] * s["n"] + 2 * s["mp"])
    per_token = 2 * _layer_params(cfg)["matmul"] + 2 * s["kc"] * s["conv"] + ssm
    return (layers * (per_token * forwarded + attn_per_key * contexts)
            + 2 * s["d"] * cfg["vocab_size"] * tokens_out)


def decode_bytes(cfg: dict) -> int:
    """Bytes of weights one decode round reads at least once: every layer's
    parameters and the head with the final norm, at the stated dtypes
    (bfloat16, with A_log, dt_bias and D in float32).  The embedding rows,
    the KV cache and the activations are left out."""
    p = _layer_params(cfg)
    per_layer = 2 * (p["matmul"] + p["conv"] + p["vectors"]) + 4 * p["f32"]
    d = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * per_layer + 2 * (d * cfg["vocab_size"] + d)
