"""Peaks of the chip, and the operations and bytes a piece of work needs.

Counts come from shapes, never from the program: a later rewrite of a
kernel or a step is read against the same yardstick.
"""

from __future__ import annotations

# Published peaks of one chip, by ``device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (bf16 and HBM figures).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(kind: str) -> dict:
    """Peaks of ``kind``; a device not in the table is an error."""
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[kind]


def gemm_ops_bytes(m: int, k: int, n: int, elem_bytes: int = 2) -> tuple:
    """Operations and bytes of an (m, k) @ (k, n) product: ``2mkn`` and
    both operands plus the output, each read or written once."""
    return 2 * m * k * n, (m * k + k * n + m * n) * elem_bytes


def roofline_share(calls, kernel_s: float, kind: str) -> float:
    """Percent of the roofline a kernel reached: the least time its calls
    could take (each the larger of its compute and bandwidth bounds) over
    the time it took.  ``calls`` is an iterable of (m, k, n)."""
    pk = peak(kind)
    least = 0.0
    for m, k, n in calls:
        ops, nbytes = gemm_ops_bytes(m, k, n)
        least += max(ops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    d, h, kv, hd, f = (cfg["hidden_size"], cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"],
                       cfg["intermediate_size"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def request_ops(cfg: dict, prompt_len: int, tokens_out: int) -> float:
    """Model operations of one served request: every prompt token and
    every output token but the last goes through the layers (weights, and
    attention over its causal context); each output token comes from one
    pass through the head.  Padding is not counted."""
    layers = cfg["num_hidden_layers"]
    attn_per_key = 4 * cfg["num_attention_heads"] * cfg["head_dim"]  # QK^T and PV
    forwarded = prompt_len + tokens_out - 1
    contexts = forwarded * (forwarded + 1) // 2  # keys seen: 1 + 2 + ... + forwarded
    return (layers * (2 * layer_matmul_params(cfg) * forwarded + attn_per_key * contexts)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"] * tokens_out)


def mfu(ops: float, seconds: float, chips: int, kind: str) -> float:
    """Percent of the chips' bf16 peak that ``ops`` in ``seconds`` are."""
    return 100.0 * ops / (seconds * chips * peak(kind)["bf16_flops"])
