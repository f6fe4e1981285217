"""VMEM/BlockSpec budget estimation (the third auditor pass).

Two estimators share one budget constant:

* :func:`estimate_pallas_calls` — *measured* from a traced jaxpr: for
  every ``pallas_call`` it sums the BlockSpec tile bytes (doubled for
  the pipeline's double buffering) and adds the peak of live
  intermediate bytes from a liveness walk of the kernel jaxpr.  This
  is what ``launch/analyze.py --report`` emits and what regenerates
  the docs/kernels.md sizing table.

* :func:`tile_footprint` — *closed-form* per (mode, n, t, tiles),
  trace-free and cheap enough to run eagerly inside
  ``engine.config.kernel_tiles`` on every dispatch.  Its per-mode
  transient models are deliberately a superset of the measured
  liveness (asserted in tests), so a tile selection that passes the
  eager gate cannot fail the traced audit on VMEM.

The ~16 MiB/core budget follows the Pallas TPU guidance; the engine
keeps headroom for the compiler's own spills via ``VMEM_BUDGET_BYTES``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
from jax.extend import core as jex_core
import numpy as np

__all__ = [
    "VMEM_BUDGET_BYTES",
    "TileBudgetError",
    "FootprintReport",
    "tile_footprint",
    "validate_tiles",
    "estimate_pallas_calls",
]

# Per-core VMEM on current TPU generations is ~16 MiB; budget the whole
# of it and let the per-mode transient models carry the safety margin.
VMEM_BUDGET_BYTES = 16 * 1024 * 1024

# Live intermediate model per mode family, in f32/u32 words (4 bytes):
# cubes are (bm, bk, bn) outer-product intermediates, planes are 2-D
# tiles materialized beside the blocks.  Chosen as a small superset of
# the traced peak liveness (tests pin traced <= modeled).
_SEQMUL_LIVE_CUBES = 8  # a3/b3 broadcasts + recurrence state words
_LUT_LIVE_ROW_PLANES = 8  # (bm, W)-wide: iota, one-hot, byte rows, stacked lhs
_LUT_LIVE_COL_PLANES = 4  # (W, bn)-wide: iota, one-hot (f32 + bf16), partial dot
_PACKED_LIVE_PLANES = 6  # even/odd lanes of both operands + partials
_MXU_LIVE_PLANES = 4  # two dot partials + accumulator temps
_DEFAULT_RANK = 8  # lowrank embedding rank (ApproxConfig default)


class TileBudgetError(ValueError):
    """A (mode, n, t) tile selection exceeds the static VMEM budget."""


@dataclasses.dataclass(frozen=True)
class FootprintReport:
    mode: str
    n: int
    t: int
    tiles: tuple
    block_bytes: int  # one grid step's BlockSpec tiles
    pipeline_bytes: int  # blocks x2 for double buffering
    transient_bytes: int  # modeled live intermediates
    total_bytes: int
    budget_bytes: int = VMEM_BUDGET_BYTES

    @property
    def within_budget(self) -> bool:
        return self.total_bytes <= self.budget_bytes


def _cube(bm: int, bn: int, bk: int) -> int:
    return bm * bk * bn * 4


def tile_footprint(mode: str, n: int, t: int, tiles: tuple) -> FootprintReport:
    """Closed-form VMEM footprint of one grid step of ``mode`` at
    ``tiles = (bm, bn, bk)`` — blocks, double-buffered pipeline copies,
    and the mode's modeled live intermediates."""
    bm, bn, bk = tiles
    operands = 2 * bm * bk + 2 * bk * bn  # mag+sign (or lane pair) tiles
    out = bm * bn
    if mode == "seqmul":
        blocks = (operands + out) * 4
        transient = _SEQMUL_LIVE_CUBES * _cube(bm, bn, bk)
    elif mode == "bitexact":
        w = max(1 << n, 128)  # one-hot width: table rows padded to a lane tile
        table = w * 2 * w * 2  # (W, 2W) bf16 byte planes pinned whole
        blocks = (operands + 2 * out) * 4 + table  # out: hi and lo byte sums
        transient = (_LUT_LIVE_ROW_PLANES * 2 * bm * w
                     + _LUT_LIVE_COL_PLANES * w * bn) * 4
    elif mode == "lowrank":
        r = _DEFAULT_RANK
        blocks = (bm * bk + bk * bn + bm * bk * r + bk * r * bn + out) * 4
        transient = _MXU_LIVE_PLANES * bm * bn * 4
    elif mode == "inject":
        blocks = (bm * bk + bk * bn + out) * 4  # packed u32 operands
        transient = _PACKED_LIVE_PLANES * (bm * bk + bk * bn) * 4 \
            + _MXU_LIVE_PLANES * bm * bn * 4
    else:
        # modes without a fused kernel (exact / fakequant / third-party
        # reference-only registrations) launch no pallas_call
        blocks = 0
        transient = 0
    pipeline = 2 * blocks
    return FootprintReport(
        mode=mode, n=n, t=t, tiles=tuple(tiles),
        block_bytes=blocks, pipeline_bytes=pipeline,
        transient_bytes=transient, total_bytes=pipeline + transient,
    )


def validate_tiles(mode: str, n: int, t: int, tiles: tuple) -> FootprintReport:
    """Eager tile validation for ``engine.config.kernel_tiles``.

    Raises :class:`TileBudgetError` naming the offending (mode, n, t)
    when a tile extent is non-positive, not a power of two, or the
    closed-form footprint exceeds :data:`VMEM_BUDGET_BYTES` — instead
    of failing later inside Pallas lowering.
    """
    bm, bn, bk = tiles
    for name, v in (("bm", bm), ("bn", bn), ("bk", bk)):
        if v <= 0:
            raise TileBudgetError(
                f"kernel_tiles(mode={mode!r}, n={n}, t={t}): tile {name}={v} "
                f"must be positive"
            )
        if v & (v - 1):
            raise TileBudgetError(
                f"kernel_tiles(mode={mode!r}, n={n}, t={t}): tile {name}={v} "
                f"must be a power of two for TPU lane alignment"
            )
    report = tile_footprint(mode, n, t, tiles)
    if not report.within_budget:
        raise TileBudgetError(
            f"kernel_tiles(mode={mode!r}, n={n}, t={t}): tiles "
            f"(bm={bm}, bn={bn}, bk={bk}) need {report.total_bytes / 2**20:.2f} "
            f"MiB of VMEM ({report.pipeline_bytes / 2**20:.2f} blocks + "
            f"{report.transient_bytes / 2**20:.2f} transient), over the "
            f"{report.budget_bytes / 2**20:.0f} MiB budget"
        )
    return report


# ------------------------------------------------------------- traced pass


def _aval_bytes(aval: Any) -> int:
    shape = getattr(aval, "shape", ())
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize if shape \
        else np.dtype(dtype).itemsize


def _is_ref(var: Any) -> bool:
    return hasattr(var.aval, "inner_aval")


def _inner_jaxprs(eqn: Any) -> list[Any]:
    out = []
    for v in eqn.params.values():
        if isinstance(v, jex_core.ClosedJaxpr):
            out.append(v.jaxpr)
        elif isinstance(v, jex_core.Jaxpr):
            out.append(v)
        elif isinstance(v, (tuple, list)):
            for e in v:
                if isinstance(e, jex_core.ClosedJaxpr):
                    out.append(e.jaxpr)
                elif isinstance(e, jex_core.Jaxpr):
                    out.append(e)
    return out


def peak_live_bytes(jaxpr: Any, *, count_inputs: bool = True) -> int:
    """Peak of live non-ref intermediate bytes over a linear walk.

    Sub-jaxprs (scan/cond bodies, jit calls) contribute their own peak
    on top of the live set at their call point — with their *inputs*
    excluded, since a call operand is the caller's buffer and is already
    counted in the caller's live set (it stays live through the call
    equation).  Refs are excluded — their bytes are the BlockSpec
    tiles, counted by the caller.
    """
    last_use: dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for a in eqn.invars:
            if isinstance(a, jex_core.Var):
                last_use[a] = i
    for v in jaxpr.outvars:
        if isinstance(v, jex_core.Var):
            last_use[v] = len(jaxpr.eqns)

    live: dict[Any, int] = {}
    if count_inputs:
        for v in list(jaxpr.invars) + list(jaxpr.constvars):
            if not _is_ref(v) and v in last_use:
                live[v] = _aval_bytes(v.aval)
    peak = sum(live.values())
    for i, eqn in enumerate(jaxpr.eqns):
        inner_peak = 0
        for inner in _inner_jaxprs(eqn):
            inner_peak = max(inner_peak,
                             peak_live_bytes(inner, count_inputs=False))
        for v in eqn.outvars:
            if not _is_ref(v):
                live[v] = _aval_bytes(v.aval)
        peak = max(peak, sum(live.values()) + inner_peak)
        for a in list(eqn.invars) + list(eqn.outvars):
            if isinstance(a, jex_core.Var) and last_use.get(a, math.inf) <= i:
                live.pop(a, None)
    return peak


def _walk_pallas(jaxpr: Any, found: list[Any]) -> None:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for inner in _inner_jaxprs(eqn):
            _walk_pallas(inner, found)


def estimate_pallas_calls(closed: jex_core.ClosedJaxpr) -> list[dict]:
    """Measured VMEM estimate for every ``pallas_call`` in a trace."""
    eqns: list[Any] = []
    _walk_pallas(closed.jaxpr, eqns)
    reports = []
    for eqn in eqns:
        gm = eqn.params["grid_mapping"]
        kernel = eqn.params["jaxpr"]
        block_bytes = 0
        for bm_ in gm.block_mappings:
            block_bytes += _aval_bytes(bm_.transformed_block_aval)
        live = peak_live_bytes(kernel)
        total = 2 * block_bytes + live
        reports.append({
            "name": eqn.params.get("name", "kernel"),
            "grid": tuple(int(g) for g in gm.grid),
            "block_bytes": int(block_bytes),
            "pipeline_bytes": int(2 * block_bytes),
            "live_bytes": int(live),
            "total_bytes": int(total),
            "budget_bytes": VMEM_BUDGET_BYTES,
            "within_budget": bool(total <= VMEM_BUDGET_BYTES),
        })
    return reports
