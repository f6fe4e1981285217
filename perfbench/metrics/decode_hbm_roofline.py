"""Model step: a lower bound on the bytes one decode round must move, over
the mean wall time of a round (``decode_s / decode_steps``), as a share of
the chip's HBM bandwidth.  The bytes are the weights every round reads
once (the family's ``decode_bytes``: every layer and the head) and the
recurrent state read and written for the live rows (``2 *
ServeStats.state_bytes * slot_utilization``).  The KV cache and the
activations are left out, so the share cannot exceed what the chip did.
A family without ``decode_bytes``, or a program without the
``state_bytes`` counter, reads nothing."""

from perfbench import readers, roofline


def read(run):
    weight_bytes = getattr(run.family, "decode_bytes", None)
    state = getattr(run.stats, "state_bytes", None)
    step_ms = readers.decode_step_ms(run)
    if weight_bytes is None or state is None or step_ms is None:
        return None
    moved = weight_bytes(run.cfg) + 2 * state * run.stats.slot_utilization
    bandwidth = roofline.peak(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * moved / (1e-3 * step_ms * bandwidth)
