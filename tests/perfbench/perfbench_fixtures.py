"""A tiny benchmark checkout for CPU tests: the real ``perfbench`` code
with tiny data files added beside it, found by name like any other."""

from __future__ import annotations

import copy
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
CELL = "tiny.exact.tiny"

_TOY = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=128, vocab_size=512)
# wide enough, and with enough near-ties in the vocabulary, that bf16
# serving and the int8 control read apart
_WIDE = dict(num_hidden_layers=4, hidden_size=256, num_attention_heads=4,
             num_key_value_heads=2, head_dim=64, intermediate_size=768, vocab_size=8192)
_FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
           "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
           "head_dim": "head_dim", "intermediate_size": "d_ff", "vocab_size": "vocab_size"}

TINY_OPEN = {
    "loop": "open", "rate_rps": 40.0, "slots": 4,
    "bucket": 32, "max_new": 16,
    "prompt": {"dist": "uniform", "min": 4, "max": 32},
    "output": {"dist": "lognormal", "mean": 8.26495, "sigma": 0.8, "min": 2, "max": 16},
    "shape_seed": 7,
}
# The wide toy cell's limits, set from CPU readings of this test size (open
# loop, 2 s, 40 of 83 requests checked) on seeds 1-11 and 2**31 + 7, each
# past the middle between the two readings:
#   widest gap: served 0.0085-0.0360, int8 control 0.0686-0.2419
#   mean gap:   served 0.000064-0.000449, int8 control 0.00175-0.00450
WIDE_LIMIT = 0.0556
WIDE_MEAN_LIMIT = 0.00123


# the two families of the benchmark's configurations: tied head with
# per-head qk-norm (Qwen3), untied head without it (Yi / Llama)
_FAMILY = {
    "qwen3-0.6b": {"rope_theta": 1000000.0, "tie_word_embeddings": True, "qk_norm": True},
    "yi-9b": {"rope_theta": 5000000.0, "tie_word_embeddings": False, "qk_norm": False},
}


def tiny_config(wide: bool, registry: str = "qwen3-0.6b") -> dict:
    sizes = _WIDE if wide else _TOY
    cfg = {"name": "tiny", "source": "test configuration at toy widths",
           "program": {"registry": registry,
                       "overrides": {_FIELDS[k]: v for k, v in sizes.items()}},
           "rms_norm_eps": 1e-06, "torch_dtype": "bfloat16"}
    cfg.update(sizes, **_FAMILY[registry])
    return cfg


def make_root(tmp: Path, *, loop: str = "open", wide: bool = False,
              registry: str = "qwen3-0.6b", check: dict = None) -> Path:
    """A checkout at ``tmp``: the real benchmark code, the program's source,
    and one tiny cell ``tiny.exact.tiny`` added as new files."""
    root = Path(tmp)
    shutil.copytree(REPO / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "perfbench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "tokens_per_s":
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    mix = copy.deepcopy(TINY_OPEN)
    if loop == "closed":
        mix.update(loop="closed", requests_per_s=20)
        del mix["rate_rps"]
    cell = {"tier": "exact",
            "check": {"requests": 40 if wide else 6, **(check or {"max_logit_gap": WIDE_LIMIT})}}
    write(root / "perfbench/configs/tiny.json", tiny_config(wide, registry))
    write(root / "perfbench/traffic/tiny.json", mix)
    write(root / f"perfbench/cells/{CELL}.json", cell)
    return root


def write(path: Path, obj) -> None:
    path.write_text(json.dumps(obj))
