"""Falcon-H1-34B-Instruct — every block runs a Mamba-2 mixer and GQA
attention side by side on one normed input and sums them, then a SwiGLU
MLP; muP multipliers on the embedding, the head, the keys, both mixers'
inputs and outputs, the five parts of the mixer's in_proj and the MLP.

Source: https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json
(72 layers, hidden 5120, 20/4 heads of 128, mamba_d_ssm 4096 as 32 heads
of 128, mamba_d_state 256, mamba_n_groups 2, conv 4 with bias, chunk 128,
gated RMSNorm after the gate, MLP 21504, vocabulary 261120, untied)."""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="falcon-h1-34b",
    family="hybrid",
    num_layers=72,
    d_model=5120,
    num_heads=20,
    num_kv_heads=4,
    head_dim=128,
    d_ff=21504,
    vocab_size=261120,
    layer_pattern=("attn_ssd",),
    ffn_activation="silu",
    rope_theta=1e11,
    conv_width=4,
    ssm_state=256,
    ssm_heads=32,
    ssm_head_dim=128,
    ssm_chunk=128,
    ssm_groups=2,
    ssm_norm=True,
    d_inner=4096,
    tie_embeddings=False,
    norm_eps=1e-5,
    embed_multiplier=5.656854249492381,
    lm_head_multiplier=0.0078125,
    key_multiplier=0.011048543456039804,
    attn_in_multiplier=1.0,
    attn_out_multiplier=0.0375,
    ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
)
