"""The Falcon-H1 family (``perfbench/families/falcon_h1.py``): its reference
agrees with the published implementation, a toy cell of it serves end to
end and reads correct, the int8 control fails the same cell, and the
``decode_hbm_roofline`` reader finds nothing to read in a dense run."""

import json
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench_fixtures import CELL, REPO, make_root, tiny_config, write

sys.path.insert(0, str(REPO))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from perfbench import harness, reference, spec, weights  # noqa: E402

PUBLISHED = json.loads((REPO / "perfbench/configs/falcon-h1-34b-8l.json").read_text())
_PROGRAM_FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "mamba_d_ssm": "d_inner", "mamba_n_heads": "ssm_heads", "mamba_d_head": "ssm_head_dim",
    "mamba_d_state": "ssm_state", "mamba_chunk_size": "ssm_chunk",
}
# A toy cell wide enough that bf16 serving and the int8 control read
# apart: with the published multipliers the embedding weighs in the
# logits about as much as 2 toy layers do, so a wider residual stream
# (the embedding's share shrinks as hidden_size ** -0.5) is needed.
TOY = dict(num_hidden_layers=2, hidden_size=1024, num_attention_heads=4,
           num_key_value_heads=2, head_dim=64, intermediate_size=768, vocab_size=8192,
           mamba_d_ssm=256, mamba_n_heads=8, mamba_d_head=32, mamba_d_state=32,
           mamba_chunk_size=8)
# The toy cell's limit, set as calibrate.py sets a cell's: open loop, an
# 8 s window, every one of its 320 requests (2,265 served tokens) checked,
# CPU readings on seeds 1-11 and 2**31 + 11.  The mean gap separates the
# two on every seed (served 2.77e-7-6.91e-7, int8 control 1.53e-6-2.72e-6),
# and the limit is the served highest + 0.6 x the distance to the
# control's lowest.  The widest gap does not separate them (served
# 1.06e-4-2.44e-4, control 2.26e-4-3.95e-4): with the published
# multipliers the embedding weighs as much in the residual stream as the
# toy's two layers, bf16 rounds that stream, and the int8 control's
# errors in the layers are scaled down by their output multipliers, so a
# single flip of a near-tie is as wide in both.  The tests run on the
# seeds that read closest to the limit (4: the served highest; 2: the
# control's lowest) and on 2**31 + 11.
TOY_SECONDS = 8.0
TOY_LIMITS = {"mean_logit_gap": 1.196e-6}


def toy_config(sizes: dict = TOY, **multipliers) -> dict:
    """The published configuration file at toy sizes, program overrides to
    match; ``multipliers`` replaces published multipliers by key."""
    cfg = dict(PUBLISHED, **sizes, **multipliers, name="tiny")
    cfg["program"] = {"registry": "falcon-h1-34b",
                      "overrides": {_PROGRAM_FIELDS[k]: v for k, v in sizes.items()}}
    return cfg


def _family(cfg):
    return spec.Spec(REPO).family(cfg)


def reference_logits(fam, cfg: dict, seed: int, tokens) -> np.ndarray:
    """The family reference's full forward over ``tokens`` (B, T), built on
    its own layer and embedding programs: float32 logits (B, T, V), the
    head in one product (small sizes only)."""
    items, key = fam._items(cfg), weights.base_key(seed)
    with jax.default_matmul_precision("highest"):
        hs = (fam._embed(items, key, jnp.asarray(tokens, jnp.int32)),)
        for layer in range(cfg["num_hidden_layers"]):
            hs = fam._layer(items, False, key, jnp.int32(layer), hs)
        top = fam._top(cfg, key)
        x = reference._rms(hs[0], 1.0 + top["final_norm"].astype(jnp.float32),
                           cfg["rms_norm_eps"])
        out = reference._mm_f32(x, top["head"].astype(jnp.float32)) * cfg["lm_head_multiplier"]
    return np.asarray(out)


def test_reference_matches_transformers_falcon_h1():
    """The family's reference against ``FalconH1ForCausalLM`` (torch, CPU,
    the naive path) with the same weights copied in: two groups, the gate
    before the norm, every multiplier away from 1 (attention_in included),
    a prompt of a chunk and a half."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    sizes = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, intermediate_size=96, vocab_size=128,
                 mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
                 mamba_chunk_size=8)
    cfg = toy_config(sizes, attention_in_multiplier=0.75)
    fam = _family(cfg)
    assert cfg["mamba_n_groups"] == 2 and cfg["mamba_norm_before_gate"] is False
    multipliers = [cfg[k] for k in ("embedding_multiplier", "lm_head_multiplier",
                                    "key_multiplier", "attention_in_multiplier",
                                    "attention_out_multiplier", "ssm_in_multiplier",
                                    "ssm_out_multiplier")]
    assert all(m != 1 for m in multipliers + cfg["ssm_multipliers"] + cfg["mlp_multipliers"])

    hf_cfg = transformers.FalconH1Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        mamba_d_ssm=cfg["mamba_d_ssm"], mamba_n_heads=cfg["mamba_n_heads"],
        mamba_d_head=cfg["mamba_d_head"], mamba_n_groups=cfg["mamba_n_groups"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_chunk_size=cfg["mamba_chunk_size"], mamba_conv_bias=True,
        mamba_proj_bias=False, mamba_rms_norm=True, mamba_norm_before_gate=False,
        projectors_bias=False, tie_word_embeddings=False,
        embedding_multiplier=cfg["embedding_multiplier"],
        lm_head_multiplier=cfg["lm_head_multiplier"], key_multiplier=cfg["key_multiplier"],
        attention_in_multiplier=cfg["attention_in_multiplier"],
        attention_out_multiplier=cfg["attention_out_multiplier"],
        ssm_in_multiplier=cfg["ssm_in_multiplier"],
        ssm_out_multiplier=cfg["ssm_out_multiplier"],
        ssm_multipliers=cfg["ssm_multipliers"], mlp_multipliers=cfg["mlp_multipliers"],
        attn_implementation="eager")
    model = transformers.FalconH1ForCausalLM(hf_cfg).eval()

    seed = 2 ** 31 + 9
    key = weights.base_key(seed)

    def t(a, transpose=False):
        a = np.asarray(a, np.float32)
        return torch.from_numpy(np.array(a.T if transpose else a, np.float32))

    top = fam._top(cfg, key)
    sd = {"model.embed_tokens.weight": t(top["embed"]), "lm_head.weight": t(top["head"], True),
          "model.final_layernorm.weight": t(1.0 + np.asarray(top["final_norm"], np.float32))}
    for i in range(cfg["num_hidden_layers"]):
        w = fam._f32_layer(fam.layer_weights(cfg, key, i))
        p = f"model.layers.{i}."
        sd.update({
            p + "input_layernorm.weight": t(w["ln1"]),
            p + "pre_ff_layernorm.weight": t(w["ln2"]),
            p + "self_attn.q_proj.weight": t(w["q"], True),
            p + "self_attn.k_proj.weight": t(w["k"], True),
            p + "self_attn.v_proj.weight": t(w["v"], True),
            p + "self_attn.o_proj.weight": t(w["o"], True),
            p + "feed_forward.gate_proj.weight": t(w["gate"], True),
            p + "feed_forward.up_proj.weight": t(w["up"], True),
            p + "feed_forward.down_proj.weight": t(w["down"], True),
            p + "mamba.in_proj.weight": t(w["in_proj"], True),
            p + "mamba.conv1d.weight": t(np.asarray(w["conv_w"]).T[:, None, :]),
            p + "mamba.conv1d.bias": t(w["conv_b"]),
            p + "mamba.dt_bias": t(w["dt_bias"]),
            p + "mamba.A_log": t(w["a_log"]),
            p + "mamba.D": t(w["d_skip"]),
            p + "mamba.norm.weight": t(w["ssm_norm"]),
            p + "mamba.out_proj.weight": t(w["out_proj"], True),
        })
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected and all("mup_vector" in m for m in missing), (missing, unexpected)

    tokens = np.random.default_rng(4).integers(0, cfg["vocab_size"], (2, 12))
    with torch.no_grad():
        want = model(torch.from_numpy(tokens)).logits.numpy()
    got = reference_logits(fam, cfg, seed, tokens)
    # Both float32, the same weights and equations; the published code runs
    # the SSM in its chunked form and the reference as the recurrence, so
    # they differ in summation order only: 1e-4 of the logits' scale.
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4 * np.abs(want).max())


def _toy_root(tmp_path, check):
    root = make_root(tmp_path / "bench", loop="open", wide=True,
                     check={"requests": 1000, **check})
    write(root / "perfbench/configs/tiny.json", toy_config())
    return root


@pytest.mark.parametrize("seed", [4, 2 ** 31 + 11])
def test_toy_cell_serves_and_reads_correct(tmp_path, seed):
    root = _toy_root(tmp_path, TOY_LIMITS)
    assert spec.Spec(root).family(toy_config()).__name__ == "perfbench_family_falcon_h1"
    result = harness.run_cell(root, CELL, seed, TOY_SECONDS, False, 0.0)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["checks"]["bad_requests"]["value"] == 0


@pytest.mark.parametrize("seed", [2, 2 ** 31 + 11])
def test_toy_cell_int8_control_fails(tmp_path, seed):
    root = _toy_root(tmp_path, TOY_LIMITS)
    result = harness.run_cell(root, CELL, seed, TOY_SECONDS, False, 0.0, control=True)
    assert not result["correct"], result["checks"]
    assert all(result["checks"][k]["value"] > v for k, v in TOY_LIMITS.items())


def test_decode_hbm_roofline_reads_nothing_in_a_dense_run():
    cfg = tiny_config(False)
    dense = _family(cfg)
    stats = SimpleNamespace(decode_s=1.0, decode_steps=100, slot_utilization=0.9,
                            state_bytes=0)
    run = harness.RunRecord(cfg=cfg, setup_s=1.0, window_s=2.0, tokens_out=10,
                            requests=(), stats=stats, device_kind="TPU v5 lite",
                            chips=1, family=dense)
    read = spec.Spec(REPO).reader("decode_hbm_roofline")
    assert read(run) is None
    # the Falcon-H1 family's bytes over a 10 ms round, 90% of the rows live
    falcon = _family(PUBLISHED)
    run.cfg, run.family = PUBLISHED, falcon
    stats.state_bytes = 10 ** 9
    moved = falcon.decode_bytes(PUBLISHED) + 2 * 10 ** 9 * 0.9
    assert read(run) == pytest.approx(100 * moved / (1e-2 * 819e9))
    # a program without the counter reads nothing
    run.stats = SimpleNamespace(decode_s=1.0, decode_steps=100, slot_utilization=0.9)
    assert read(run) is None


def test_published_sizes_give_34b_parameters_and_the_stage_bytes():
    fam = _family(PUBLISHED)
    whole = dict(PUBLISHED, num_hidden_layers=72)
    p = fam._layer_params(PUBLISHED)
    layer = sum(p.values())
    vocab_rows = 2 * PUBLISHED["vocab_size"] * PUBLISHED["hidden_size"]  # embedding, head
    assert 33.5e9 < 72 * layer + vocab_rows < 33.8e9
    # one stage streams its 8 layers and the head every decode round
    assert 9.5e9 < fam.decode_bytes(PUBLISHED) < 9.6e9
    assert fam.decode_bytes(whole) > 9 * fam.decode_bytes(PUBLISHED) - 9 * 2.7e9
    with pytest.raises(ValueError, match="mamba_norm_before_gate"):
        fam.program_config(dict(PUBLISHED, mamba_norm_before_gate=True))
    with pytest.raises(ValueError, match="program ssm_groups=2"):
        fam.program_config(dict(PUBLISHED, mamba_n_groups=1))
    assert jax.numpy.dtype(fam.program_config(PUBLISHED).dtype) == jax.numpy.bfloat16
