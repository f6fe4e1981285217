"""Trace specifications: what to trace and under which input contract.

A :class:`TraceSpec` is the unit the auditor consumes: a callable plus
abstract input shapes and the *value contract* of each input (e.g. a
quantized magnitude plane is ``[0, 2^n - 1]`` and integer-valued, not
the full uint32 carrier range).  Kernel modules export colocated
``audit_trace_*`` builders returning these, so the contract lives next
to the code it describes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Sequence

import jax
from jax.extend import core as jex_core
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ValueRange:
    """Value contract for one traced input.

    ``lo``/``hi`` bound the elementwise values; ``int_valued`` asserts
    every element is a mathematical integer (regardless of carrier
    dtype — quantized magnitudes stored in f32 are still int-valued).
    """

    lo: float
    hi: float
    int_valued: bool = False

    @staticmethod
    def quantized(n: int) -> "ValueRange":
        """Magnitude plane of an n-bit quantizer: ``[0, 2^n - 1]``."""
        return ValueRange(0.0, float((1 << n) - 1), int_valued=True)

    @staticmethod
    def sign() -> "ValueRange":
        return ValueRange(-1.0, 1.0, int_valued=True)

    @staticmethod
    def carrier(dtype: Any) -> "ValueRange":
        """The full range representable by ``dtype`` (no contract)."""
        dt = jnp.dtype(dtype)
        if dt == jnp.dtype(jnp.bool_):
            return ValueRange(0.0, 1.0, int_valued=True)
        if jnp.issubdtype(dt, jnp.integer):
            info = jnp.iinfo(dt)
            return ValueRange(float(info.min), float(info.max), int_valued=True)
        return ValueRange(-math.inf, math.inf, int_valued=False)


@dataclasses.dataclass(frozen=True)
class TraceSpec:
    """One auditable trace: a callable, its abstract inputs, a contract.

    ``fn`` is traced with ``jax.make_jaxpr`` over ``args`` (which are
    ``jax.ShapeDtypeStruct``s or concrete arrays closed over as
    constants) — abstract eval only, nothing executes.  ``ranges`` maps
    positionally onto ``args``; ``None`` entries fall back to the
    carrier range of the arg dtype.
    """

    name: str
    fn: Callable[..., Any]
    args: Sequence[Any]
    ranges: Sequence[ValueRange | None] = ()
    # Whether integer-valued f32 intermediates must stay exactly
    # representable (< 2^24) *before* any reduction.  True for the
    # bit-exact parity contract (seqmul / LUT assembly); False for
    # float-valued paths (lowrank correction, fakequant).
    exact_products: bool = True
    # Output contracts: the caller-facing claim each traced output must
    # satisfy (positionally; None = unconstrained).  An output whose
    # derived envelope can leave its contract is a gating "contract"
    # finding — e.g. the packed single-u32 product is consumed as a
    # non-negative int32 LUT payload, so its contract is
    # ``[0, 2^31 - 1]``; the envelope leaves it exactly when 2n > 31.
    out_ranges: Sequence[ValueRange | None] = ()
    # Why each output contract holds/matters, for findings (optional).
    out_contract_reason: str = ""

    def trace(self) -> jex_core.ClosedJaxpr:
        return jax.make_jaxpr(self.fn)(*self.args)

    def input_ranges(self) -> list[ValueRange]:
        out: list[ValueRange] = []
        ranges = list(self.ranges) + [None] * (len(self.args) - len(self.ranges))
        for arg, rng in zip(self.args, ranges):
            if rng is not None:
                out.append(rng)
            else:
                out.append(ValueRange.carrier(arg.dtype))
        return out


def sds(shape: Sequence[int], dtype: Any) -> jax.ShapeDtypeStruct:
    """Shorthand for an abstract traced input."""
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
