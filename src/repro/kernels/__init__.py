"""Fused Pallas kernels for the approximate multiplier (docs/kernels.md)."""

SUBLANE = 8  # rows of one vreg tile: the M extent of a block is a multiple


def row_block(bm: int, m: int) -> int:
    """The M block for ``m`` rows: ``bm``, or ``m`` rounded up to the
    sublane tile when that is smaller, so a decode step's few rows are
    not padded to a prefill-sized block."""
    return min(bm, -(-m // SUBLANE) * SUBLANE)
