"""Whole step: model operations of the requests served (prompt tokens and
output tokens, no padding; the same count for every tier), as the
configuration's family counts them, over the window, as a share of the
chip's bf16 peak."""

from perfbench import roofline


def read(run):
    ops = sum(run.family.request_ops(run.cfg, r.prompt_len, r.tokens_out) for r in run.requests)
    return roofline.mfu(ops, run.window_s, run.chips, run.device_kind)
