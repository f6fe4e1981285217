"""The dense family: Qwen3 / Llama-style decoders with grouped-query
attention and a SwiGLU feed-forward in every layer.

Its weights are ``perfbench.weights.program_params``, its reference and
int8 control ``perfbench.reference.gaps``, and its operation count
``perfbench.roofline.request_ops``.  A configuration file that names no
``family`` belongs here.
"""

from __future__ import annotations

from perfbench import reference, roofline, weights

# The published keys a configuration file states, and the field of the
# program's ModelConfig each must equal.
_SIZES = {
    "hidden_size": "d_model", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "qk_norm": "use_qk_norm",
    "torch_dtype": "dtype",
}
# What the reference implements; a program config with anything else is
# not the model the file describes.
_PLAIN = {"layer_pattern": ("attn_global",), "ffn_activation": "silu",
          "use_post_norm": False, "embed_scale": False, "num_experts": 0,
          "final_logit_softcap": None, "attn_logit_softcap": None,
          "use_mrope": False, "encoder_layers": 0, "scan_layers": True}


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file, checked against
    every size the file states."""
    from repro.configs.registry import get_config

    prog = get_config(cfg["program"]["registry"], **cfg["program"].get("overrides", {}))
    for key, field in _SIZES.items():
        if getattr(prog, field) != cfg[key]:
            raise ValueError(f"{cfg['name']}: program {field}={getattr(prog, field)!r} "
                             f"but the configuration states {key}={cfg[key]!r}")
    for field, want in _PLAIN.items():
        if getattr(prog, field) != want:
            raise ValueError(f"{cfg['name']}: program {field}={getattr(prog, field)!r}, "
                             f"the reference implements {want!r}")
    return prog


program_params = weights.program_params
gaps = reference.gaps
request_ops = roofline.request_ops
