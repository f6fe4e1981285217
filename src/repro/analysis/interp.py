"""Interval abstract interpretation of jaxprs (the overflow/gather passes).

Walks a traced :class:`jax.extend.core.ClosedJaxpr` with every value summarized
by a :class:`repro.analysis.domain.Interval` — O(1) work per equation
regardless of tensor shape, so auditing realistic kernel envelopes is
cheap.  Three families of checks fire as equations are interpreted:

* **carrier overflow** — an integer-dtype result whose mathematical
  envelope leaves its carrier range.  Signed shifts are treated as
  defined-modular (the packed kernel's ``(w << 16) >> 16`` lane
  extraction is intentional); *unsigned* wraparound is a finding.
  Output *contracts* (:func:`check_output_contract`) extend this to
  caller-facing claims that bind before any carrier wraps — the packed
  product tops out at ``2^{2n} - 1`` (inside uint32 even at n=16) but
  its int32-payload contract breaks there, rediscovering ``2n <= 31``.
* **f32 exactness** — an integer-valued float32 whose *pre-reduction*
  magnitude exceeds ``2^24`` cannot represent every integer it may
  take, breaking the bit-exact parity contract.  Assembled seqmul
  products are ``< 2^{2n}``, so this rediscovers the ``n <= 12``
  seqmul bound.  Reduction *accumulators* scale with K and are
  reported as a derived ``k_exact`` envelope instead of gated,
  matching the parity model in docs/kernels.md.
* **gather bounds** — every ``gather`` index interval must lie inside
  ``[0, dim - slice]`` of its table.  The online-softmax probabilities
  are proven in ``[0, 1]`` via a dominance refinement (``reduce_max``
  results dominate their operand; ``exp(x - m) <= 1`` when ``m``
  dominates ``x``), which closes the ``U[p_int]`` attention gather.

``pallas_call`` is interpreted by modeling kernel refs as mutable
cells: input refs start at the outer operand interval, output and
scratch refs start uninitialized, writes *join* into the cell (sound
for revisited accumulator tiles).  The innermost grid axis — the K
revisit axis in every GEMM kernel here — is unrolled with a precise
``program_id``, so ``k == 0`` initialization branches resolve exactly.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
from jax.extend import core as jex_core
import numpy as np

from repro.analysis import domain
from repro.analysis.domain import F32_EXACT_INT, Interval
from repro.analysis.spec import TraceSpec

_INF = math.inf

# Finding kinds that block certification.  "note" is informational;
# "unknown" is gating because an unmodeled primitive means the proof
# does not cover the kernel.
GATING_KINDS = frozenset(
    {"overflow", "exactness", "gather", "unknown", "vmem-budget",
     "trace-rejected", "contract"})


# f32 arithmetic whose mathematical result may not be representable;
# everything else (rounding, clamping, selection, structural ops) only
# produces values that are representable by construction.
_EXACTNESS_PRIMS = frozenset({"mul", "add", "sub", "dot_general"})


@dataclasses.dataclass(frozen=True)
class Finding:
    kind: str
    message: str
    where: str = ""

    @property
    def gating(self) -> bool:
        return self.kind in GATING_KINDS


@dataclasses.dataclass(frozen=True)
class AuditPolicy:
    # Gate unreduced integer-valued f32 values above 2^24 (bit-exact
    # parity contract).  Off for float-valued modes (lowrank/fakequant).
    exact_products: bool = True
    # Unroll caps; exceeding them widens (sound, less precise).
    grid_cap: int = 64
    scan_cap: int = 128
    while_cap: int = 64


@dataclasses.dataclass
class InterpReport:
    findings: list[Finding]
    facts: dict[str, Any]

    @property
    def gating_findings(self) -> list[Finding]:
        return [f for f in self.findings if f.gating]

    @property
    def certified(self) -> bool:
        return not self.gating_findings


class _RefCell:
    """Mutable abstract state of one pallas ref (None = uninitialized)."""

    __slots__ = ("av", "dtype")

    def __init__(self, dtype: Any, av: Interval | None = None):
        self.av = av
        self.dtype = dtype

    def read(self) -> Interval:
        return self.av if self.av is not None else Interval.of_dtype(self.dtype)

    def write(self, val: Interval) -> None:
        # Dominance claims reference jaxpr vars of the *current* unrolled
        # step; a value read back on a later step must not carry them
        # (the same vars will hold different values there).
        val = val.with_(dominates=frozenset())
        self.av = val if self.av is None else self.av.join(val)


def _const_interval(c: Any) -> Interval:
    arr = np.asarray(c)
    if arr.size == 0:
        return Interval.point(0.0)
    if arr.dtype == np.bool_:
        return Interval(float(arr.min()), float(arr.max()), int_valued=True)
    lo, hi = float(arr.min()), float(arr.max())
    int_valued = np.issubdtype(arr.dtype, np.integer)
    if not int_valued and arr.size <= (1 << 22) and np.all(np.isfinite(arr)):
        # Integrality above 2^24 is vacuous for floats (every
        # representable f32 there is an integer) and would make mask
        # sentinels like -2.38e38 look like wide-integer arithmetic.
        int_valued = bool(np.all(np.mod(arr, 1.0) == 0.0)
                          and max(abs(lo), abs(hi)) <= F32_EXACT_INT)
    return Interval(lo, hi, int_valued=int_valued)


def _clamp_to(iv: Interval, dtype: Any) -> Interval:
    full = Interval.of_dtype(dtype)
    lo = max(iv.lo, full.lo)
    hi = min(iv.hi, full.hi)
    if lo > hi:  # envelope entirely out of carrier: wraps to full range
        return full
    return Interval(lo, hi, int_valued=iv.int_valued or full.int_valued,
                    reduced=iv.reduced, dominates=iv.dominates)


def _is_integer_dtype(dtype: Any) -> bool:
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    return jnp.issubdtype(dt, jnp.integer)


def _is_unsigned_dtype(dtype: Any) -> bool:
    import jax.numpy as jnp

    return jnp.issubdtype(jnp.dtype(dtype), jnp.unsignedinteger)


def _is_f32(dtype: Any) -> bool:
    import jax.numpy as jnp

    return jnp.dtype(dtype) == jnp.dtype(jnp.float32)


def _point_f32_exact(iv: Interval) -> bool:
    """A point interval whose single value round-trips through f32 is
    exactly representable no matter its magnitude (e.g. the causal-mask
    fill constant, a large integral f32 literal)."""
    return iv.is_point and float(np.float32(iv.lo)) == iv.lo


class Interpreter:
    def __init__(self, policy: AuditPolicy):
        self.policy = policy
        self.findings: list[Finding] = []
        self.facts: dict[str, Any] = {
            "gathers_checked": 0,
            "gathers_proven": 0,
            "k_exact": None,
            "max_unreduced_int_f32": 0.0,
        }
        self.stack: list[str] = []

    # -- bookkeeping -------------------------------------------------
    def _where(self) -> str:
        return "/".join(self.stack)

    def _finding(self, kind: str, message: str) -> None:
        self.findings.append(Finding(kind, message, self._where()))

    def _note_k_exact(self, per_term_mag: float) -> None:
        if per_term_mag <= 0 or not math.isfinite(per_term_mag):
            return
        k = int(F32_EXACT_INT // max(1.0, per_term_mag))
        prev = self.facts["k_exact"]
        self.facts["k_exact"] = k if prev is None else min(prev, k)

    # -- environment -------------------------------------------------
    def _read(self, env: dict, atom: Any) -> Any:
        if isinstance(atom, jex_core.Literal):
            return _const_interval(atom.val)
        return env[atom]

    def _land(self, env: dict, eqn: Any, outvar: Any, iv: Interval) -> None:
        """Bind an equation result, running the overflow/exactness checks."""
        aval = outvar.aval
        dtype = getattr(aval, "dtype", None)
        if dtype is None:
            env[outvar] = iv
            return
        if _is_integer_dtype(dtype):
            if not iv.fits(dtype):
                # Signed left shifts are defined-modular lane surgery
                # here ((w << 16) >> 16); bitwise ops are closed over
                # their carrier, so an out-of-carrier envelope on them
                # is domain imprecision, never a semantic overflow.
                exempt = (eqn.primitive.name in ("or", "and", "xor", "not")
                          or (eqn.primitive.name == "shift_left"
                              and not _is_unsigned_dtype(dtype)))
                if not exempt:
                    self._finding(
                        "overflow",
                        f"{eqn.primitive.name}: envelope [{iv.lo:.6g}, {iv.hi:.6g}] "
                        f"leaves {np.dtype(dtype).name} carrier range",
                    )
                iv = _clamp_to(iv, dtype)
        elif _is_f32(dtype) and iv.int_valued and not iv.reduced:
            mag = iv.magnitude()
            if math.isfinite(mag):
                self.facts["max_unreduced_int_f32"] = max(
                    self.facts["max_unreduced_int_f32"], mag)
            # Only value-constructing arithmetic can silently round: a
            # round/floor/ceil result is representable by construction
            # (every f32 >= 2^24 is already an integer), and joins/
            # selections only repeat already-checked values.
            constructs = eqn.primitive.name in _EXACTNESS_PRIMS
            if (constructs and self.policy.exact_products
                    and mag > F32_EXACT_INT and not _point_f32_exact(iv)):
                self._finding(
                    "exactness",
                    f"{eqn.primitive.name}: integer-valued f32 envelope "
                    f"[{iv.lo:.6g}, {iv.hi:.6g}] exceeds exactly-representable "
                    f"2^24 before any reduction",
                )
                iv = iv.with_(int_valued=False)
        env[outvar] = iv

    # -- jaxpr walk --------------------------------------------------
    def run_closed(self, closed: jex_core.ClosedJaxpr, args: list[Any]) -> list[Any]:
        consts = [_const_interval(c) for c in closed.consts]
        return self.run(closed.jaxpr, consts, args)

    def run(self, jaxpr: Any, consts: list[Any], args: list[Any]) -> list[Any]:
        env: dict[Any, Any] = {}
        for v, c in zip(jaxpr.constvars, consts):
            env[v] = c
        for v, a in zip(jaxpr.invars, args):
            env[v] = a
        for eqn in jaxpr.eqns:
            self.eqn(env, eqn)
        return [self._read(env, v) for v in jaxpr.outvars]

    def eqn(self, env: dict, eqn: Any) -> None:
        name = eqn.primitive.name
        handler = _HANDLERS.get(name)
        if handler is not None:
            handler(self, env, eqn)
            return
        self._finding(
            "unknown",
            f"primitive {name!r} is not modeled by the auditor",
        )
        for ov in eqn.outvars:
            dtype = getattr(ov.aval, "dtype", None)
            env[ov] = Interval.of_dtype(dtype) if dtype is not None else Interval(-_INF, _INF)

    # -- sub-jaxpr descent -------------------------------------------
    def _descend(self, closed: Any, args: list[Any], tag: str) -> list[Any]:
        self.stack.append(tag)
        try:
            if hasattr(closed, "consts"):
                outs = self.run_closed(closed, args)
            else:
                outs = self.run(closed, [], args)
        finally:
            self.stack.pop()
        # Dominance sets name sub-jaxpr-local vars; strip them at the
        # boundary (also breaks stale claims across scan iterations,
        # where the same body vars rebind to new values).
        return [o.with_(dominates=frozenset()) if isinstance(o, Interval) else o
                for o in outs]


def check_output_contract(spec: TraceSpec, outs: list[Any]) -> list[Finding]:
    """Check traced output envelopes against the spec's ``out_ranges``.

    The contract is the *caller-facing claim* about the kernel's result
    (e.g. "the packed product is a non-negative int32 payload"); an
    envelope that can leave it is a gating finding even when no carrier
    dtype wraps — this is how the packed ``2n <= 31`` bound is
    rediscovered, since the packed word tops out at ``2^{2n} - 1`` and
    first exceeds the int32 payload contract at ``n = 16``.
    """
    findings: list[Finding] = []
    for i, (out, rng) in enumerate(zip(outs, spec.out_ranges)):
        if rng is None or not isinstance(out, Interval):
            continue
        if out.lo < rng.lo or out.hi > rng.hi:
            why = f" ({spec.out_contract_reason})" if spec.out_contract_reason else ""
            findings.append(Finding(
                "contract",
                f"output {i} envelope [{out.lo:.6g}, {out.hi:.6g}] can leave "
                f"its declared contract [{rng.lo:.6g}, {rng.hi:.6g}]{why}",
                spec.name,
            ))
    return findings


def interpret(spec: TraceSpec, policy: AuditPolicy | None = None) -> InterpReport:
    """Trace ``spec`` and abstractly interpret it under its contract."""
    if policy is None:
        policy = AuditPolicy(exact_products=spec.exact_products)
    closed = spec.trace()
    args = [
        Interval(r.lo, r.hi, int_valued=r.int_valued)
        for r in spec.input_ranges()
    ]
    it = Interpreter(policy)
    it.stack.append(spec.name)
    outs = it.run_closed(closed, args)
    it.findings.extend(check_output_contract(spec, outs))
    return InterpReport(findings=it.findings, facts=it.facts)


def interpret_closed(
    closed: jex_core.ClosedJaxpr,
    args: list[Interval],
    policy: AuditPolicy | None = None,
) -> InterpReport:
    it = Interpreter(policy or AuditPolicy())
    it.run_closed(closed, args)
    return InterpReport(findings=it.findings, facts=it.facts)


# ---------------------------------------------------------------------
# primitive handlers
# ---------------------------------------------------------------------

_HANDLERS: dict[str, Callable[[Interpreter, dict, Any], None]] = {}


def _register(*names: str):
    def deco(fn):
        for n in names:
            _HANDLERS[n] = fn
        return fn

    return deco


def _in(self: Interpreter, env: dict, eqn: Any) -> list[Any]:
    return [self._read(env, a) for a in eqn.invars]


def _unary_identity(self, env, eqn):
    (a,) = _in(self, env, eqn)
    self._land(env, eqn, eqn.outvars[0], a)


_register("copy", "stop_gradient", "reduce_precision", "real")(_unary_identity)


@_register("broadcast_in_dim", "reshape", "squeeze", "expand_dims")
def _structural(self, env, eqn):
    (a, *_rest) = _in(self, env, eqn)
    # elementwise-identical: dominance survives
    self._land(env, eqn, eqn.outvars[0], a)


@_register("transpose", "rev", "slice", "dynamic_slice")
def _permute(self, env, eqn):
    a = self._read(env, eqn.invars[0])
    self._land(env, eqn, eqn.outvars[0], a.with_(dominates=frozenset()))


@_register("concatenate")
def _concat(self, env, eqn):
    ivs = _in(self, env, eqn)
    self._land(env, eqn, eqn.outvars[0], domain.join_all(ivs))


@_register("pad")
def _pad(self, env, eqn):
    op, padval = _in(self, env, eqn)
    self._land(env, eqn, eqn.outvars[0], op.join(padval))


@_register("dynamic_update_slice")
def _dus(self, env, eqn):
    op, upd, *_idx = _in(self, env, eqn)
    self._land(env, eqn, eqn.outvars[0], op.join(upd))


@_register("iota")
def _iota(self, env, eqn):
    dim = eqn.params["dimension"]
    shape = eqn.params["shape"]
    hi = max(0, shape[dim] - 1)
    self._land(env, eqn, eqn.outvars[0], Interval(0.0, float(hi), int_valued=True))


@_register("add")
def _add(self, env, eqn):
    a, b = _in(self, env, eqn)
    self._land(env, eqn, eqn.outvars[0], domain.add(a, b))


@_register("sub")
def _sub(self, env, eqn):
    a, b = _in(self, env, eqn)
    out = domain.sub(a, b)
    # dominance refinement: if b is a running max over a, then a - b <= 0
    a_var = eqn.invars[0]
    if not isinstance(a_var, jex_core.Literal) and a_var in b.dominates:
        out = Interval(min(out.lo, 0.0), min(out.hi, 0.0),
                       int_valued=out.int_valued, reduced=out.reduced)
    self._land(env, eqn, eqn.outvars[0], out)


@_register("mul")
def _mul(self, env, eqn):
    a, b = _in(self, env, eqn)
    self._land(env, eqn, eqn.outvars[0], domain.mul(a, b))


@_register("div")
def _div(self, env, eqn):
    a, b = _in(self, env, eqn)
    self._land(env, eqn, eqn.outvars[0], domain.div(a, b))


@_register("rem")
def _rem(self, env, eqn):
    a, b = _in(self, env, eqn)
    m = b.magnitude()
    if a.lo >= 0:
        out = Interval(0.0, min(a.hi, m), int_valued=a.int_valued and b.int_valued)
    else:
        out = Interval(-m, m, int_valued=a.int_valued and b.int_valued)
    self._land(env, eqn, eqn.outvars[0], out)


@_register("max")
def _max(self, env, eqn):
    a, b = _in(self, env, eqn)
    dominated = frozenset(
        v for v in eqn.invars if not isinstance(v, jex_core.Literal))
    self._land(env, eqn, eqn.outvars[0], domain.max_(a, b, dominated))


@_register("min")
def _min(self, env, eqn):
    a, b = _in(self, env, eqn)
    self._land(env, eqn, eqn.outvars[0], domain.min_(a, b))


@_register("neg")
def _neg(self, env, eqn):
    (a,) = _in(self, env, eqn)
    self._land(env, eqn, eqn.outvars[0],
               Interval(-a.hi, -a.lo, int_valued=a.int_valued, reduced=a.reduced))


@_register("abs")
def _abs(self, env, eqn):
    (a,) = _in(self, env, eqn)
    if a.lo >= 0:
        out = a.with_(dominates=frozenset())
    elif a.hi <= 0:
        out = Interval(-a.hi, -a.lo, int_valued=a.int_valued, reduced=a.reduced)
    else:
        out = Interval(0.0, a.magnitude(), int_valued=a.int_valued, reduced=a.reduced)
    self._land(env, eqn, eqn.outvars[0], out)


@_register("sign")
def _sign(self, env, eqn):
    (a,) = _in(self, env, eqn)
    lo = -1.0 if a.lo < 0 else 0.0 if a.lo == 0 else 1.0
    hi = 1.0 if a.hi > 0 else 0.0 if a.hi == 0 else -1.0
    self._land(env, eqn, eqn.outvars[0], Interval(lo, hi, int_valued=True))


@_register("floor")
def _floor(self, env, eqn):
    (a,) = _in(self, env, eqn)
    lo = math.floor(a.lo) if math.isfinite(a.lo) else a.lo
    hi = math.floor(a.hi) if math.isfinite(a.hi) else a.hi
    self._land(env, eqn, eqn.outvars[0],
               Interval(lo, hi, int_valued=True, reduced=a.reduced))


@_register("ceil", "round")
def _round(self, env, eqn):
    (a,) = _in(self, env, eqn)
    lo = math.floor(a.lo) if math.isfinite(a.lo) else a.lo
    hi = math.ceil(a.hi) if math.isfinite(a.hi) else a.hi
    self._land(env, eqn, eqn.outvars[0],
               Interval(lo, hi, int_valued=True, reduced=a.reduced))


@_register("clamp")
def _clamp(self, env, eqn):
    lo_iv, x, hi_iv = _in(self, env, eqn)
    lo = max(x.lo, lo_iv.lo)
    hi = min(x.hi, hi_iv.hi)
    if lo > hi:
        lo, hi = lo_iv.lo, hi_iv.hi
    self._land(env, eqn, eqn.outvars[0],
               Interval(lo, hi,
                        int_valued=x.int_valued and lo_iv.int_valued and hi_iv.int_valued,
                        reduced=x.reduced))


@_register("integer_pow")
def _integer_pow(self, env, eqn):
    (a,) = _in(self, env, eqn)
    y = eqn.params["y"]
    cands = [a.lo ** y, a.hi ** y]
    if y % 2 == 0 and a.lo < 0 < a.hi:
        cands.append(0.0)
    self._land(env, eqn, eqn.outvars[0],
               Interval(min(cands), max(cands), int_valued=a.int_valued and y >= 0,
                        reduced=a.reduced))


def _erf_inv(v: float) -> float:
    """Monotone inverse of ``math.erf`` by bisection (interval endpoints
    only — precision well beyond what an envelope needs)."""
    lo, hi = -10.0, 10.0
    for _ in range(80):
        mid = (lo + hi) / 2
        if math.erf(mid) < v:
            lo = mid
        else:
            hi = mid
    return (lo + hi) / 2


def _monotone(fn):
    def handler(self, env, eqn):
        (a, *_rest) = _in(self, env, eqn)
        self._land(env, eqn, eqn.outvars[0], domain.monotone_unary(a, fn))

    return handler


_register("exp")(_monotone(math.exp))
_register("exp2")(_monotone(lambda v: 2.0 ** v))
_register("log")(_monotone(lambda v: math.log(v) if v > 0 else -_INF))
_register("log1p")(_monotone(lambda v: math.log1p(v) if v > -1 else -_INF))
_register("expm1")(_monotone(math.expm1))
_register("tanh")(_monotone(math.tanh))
_register("logistic")(_monotone(lambda v: 1.0 / (1.0 + math.exp(-min(v, 700.0)))))
_register("erf")(_monotone(math.erf))
_register("erf_inv")(_monotone(lambda v: -_INF if v <= -1 else _INF if v >= 1 else
                               _erf_inv(v)))
_register("sqrt")(_monotone(lambda v: math.sqrt(v) if v >= 0 else 0.0))
_register("rsqrt")(_monotone(lambda v: 1.0 / math.sqrt(v) if v > 0 else _INF))


@_register("shift_left")
def _shift_left(self, env, eqn):
    a, s = _in(self, env, eqn)
    self._land(env, eqn, eqn.outvars[0], domain.shift_left(a, s))


@_register("shift_right_logical", "shift_right_arithmetic")
def _shift_right(self, env, eqn):
    a, s = _in(self, env, eqn)
    self._land(env, eqn, eqn.outvars[0], domain.shift_right(a, s))


def _is_bool(atom) -> bool:
    import jax.numpy as jnp

    return jnp.dtype(atom.aval.dtype) == jnp.dtype(jnp.bool_)


@_register("and")
def _and(self, env, eqn):
    a, b = _in(self, env, eqn)
    if _is_bool(eqn.outvars[0]):
        if a.is_point and b.is_point:
            out = Interval.point(float(bool(a.lo) and bool(b.lo)))
        else:
            out = Interval.bool01()
    else:
        out = domain.bit_and(a, b)
    self._land(env, eqn, eqn.outvars[0], out)


@_register("or", "xor")
def _or(self, env, eqn):
    a, b = _in(self, env, eqn)
    if _is_bool(eqn.outvars[0]):
        out = Interval.bool01()
        if a.is_point and b.is_point:
            av, bv = bool(a.lo), bool(b.lo)
            out = Interval.point(
                float(av or bv if eqn.primitive.name == "or" else av != bv))
    else:
        out = domain.bit_or(a, b, is_xor=eqn.primitive.name == "xor")
    self._land(env, eqn, eqn.outvars[0], out)


@_register("not")
def _not(self, env, eqn):
    (a,) = _in(self, env, eqn)
    if _is_bool(eqn.outvars[0]):
        out = (Interval.point(float(not bool(a.lo))) if a.is_point
               else Interval.bool01())
    else:
        out = Interval.of_dtype(eqn.outvars[0].aval.dtype)
    self._land(env, eqn, eqn.outvars[0], out)


def _cmp(self, env, eqn, certain_true, certain_false):
    a, b = _in(self, env, eqn)
    if certain_true(a, b):
        out = Interval.point(1.0)
    elif certain_false(a, b):
        out = Interval.point(0.0)
    else:
        out = Interval.bool01()
    self._land(env, eqn, eqn.outvars[0], out)


_register("eq")(lambda s, e, q: _cmp(
    s, e, q,
    lambda a, b: a.is_point and b.is_point and a.lo == b.lo,
    lambda a, b: a.hi < b.lo or b.hi < a.lo))
_register("ne")(lambda s, e, q: _cmp(
    s, e, q,
    lambda a, b: a.hi < b.lo or b.hi < a.lo,
    lambda a, b: a.is_point and b.is_point and a.lo == b.lo))
_register("lt")(lambda s, e, q: _cmp(
    s, e, q, lambda a, b: a.hi < b.lo, lambda a, b: a.lo >= b.hi))
_register("le")(lambda s, e, q: _cmp(
    s, e, q, lambda a, b: a.hi <= b.lo, lambda a, b: a.lo > b.hi))
_register("gt")(lambda s, e, q: _cmp(
    s, e, q, lambda a, b: a.lo > b.hi, lambda a, b: a.hi <= b.lo))
_register("ge")(lambda s, e, q: _cmp(
    s, e, q, lambda a, b: a.lo >= b.hi, lambda a, b: a.hi < b.lo))


@_register("select_n")
def _select_n(self, env, eqn):
    pred, *cases = _in(self, env, eqn)
    if pred.is_point and 0 <= int(pred.lo) < len(cases):
        out = cases[int(pred.lo)]
    else:
        out = domain.join_all(cases)
    self._land(env, eqn, eqn.outvars[0], out)


@_register("convert_element_type")
def _convert(self, env, eqn):
    (a,) = _in(self, env, eqn)
    new_dtype = eqn.params["new_dtype"]
    out = a
    if _is_integer_dtype(new_dtype):
        if not a.int_valued:
            # float->int conversion truncates toward zero
            lo = math.floor(a.lo) if math.isfinite(a.lo) else a.lo
            hi = math.ceil(a.hi) if math.isfinite(a.hi) else a.hi
            out = Interval(lo, hi, int_valued=True, reduced=a.reduced)
        else:
            out = a.with_(int_valued=True, dominates=frozenset())
    else:
        # int->float: exactness of wide integers is checked here, since a
        # 2n-bit assembled product first becomes inexact at this cast.
        if (a.int_valued and not a.reduced and self.policy.exact_products
                and _is_f32(new_dtype) and a.magnitude() > F32_EXACT_INT
                and not _point_f32_exact(a)):
            self._finding(
                "exactness",
                f"convert_element_type: integer envelope [{a.lo:.6g}, {a.hi:.6g}] "
                f"is not exactly representable in float32 (> 2^24)",
            )
            out = a.with_(int_valued=False, dominates=frozenset())
        else:
            out = a.with_(dominates=a.dominates if _is_f32(new_dtype) else frozenset())
    self._land(env, eqn, eqn.outvars[0], out)


@_register("bitcast_convert_type")
def _bitcast(self, env, eqn):
    new_dtype = eqn.params["new_dtype"]
    self._land(env, eqn, eqn.outvars[0], Interval.of_dtype(new_dtype))


# -- reductions ------------------------------------------------------


def _axes_size(eqn, operand_index: int = 0) -> int:
    shape = eqn.invars[operand_index].aval.shape
    axes = eqn.params["axes"]
    n = 1
    for ax in axes:
        n *= shape[ax]
    return max(n, 1)


@_register("reduce_sum")
def _reduce_sum(self, env, eqn):
    (a,) = _in(self, env, eqn)
    n = _axes_size(eqn)
    out = Interval(a.lo * n, a.hi * n, int_valued=a.int_valued,
                   reduced=a.reduced or n > 1)
    if n > 1 and a.int_valued and _is_f32(eqn.invars[0].aval.dtype):
        self._note_k_exact(a.magnitude())
    self._land(env, eqn, eqn.outvars[0], out)


@_register("reduce_max")
def _reduce_max(self, env, eqn):
    (a,) = _in(self, env, eqn)
    dominated = frozenset(
        v for v in eqn.invars if not isinstance(v, jex_core.Literal))
    self._land(env, eqn, eqn.outvars[0],
               a.with_(dominates=a.dominates | dominated))


@_register("reduce_min")
def _reduce_min(self, env, eqn):
    (a,) = _in(self, env, eqn)
    self._land(env, eqn, eqn.outvars[0], a.with_(dominates=frozenset()))


@_register("reduce_and", "reduce_or")
def _reduce_bool(self, env, eqn):
    self._land(env, eqn, eqn.outvars[0], Interval.bool01())


@_register("argmax", "argmin")
def _argmax(self, env, eqn):
    n = _axes_size(eqn)
    self._land(env, eqn, eqn.outvars[0],
               Interval(0.0, float(n - 1), int_valued=True))


@_register("cumsum")
def _cumsum(self, env, eqn):
    (a,) = _in(self, env, eqn)
    axis = eqn.params["axis"]
    n = max(eqn.invars[0].aval.shape[axis], 1)
    out = Interval(min(a.lo, a.lo * n), max(a.hi, a.hi * n),
                   int_valued=a.int_valued, reduced=a.reduced or n > 1)
    self._land(env, eqn, eqn.outvars[0], out)


@_register("cummax")
def _cummax(self, env, eqn):
    (a,) = _in(self, env, eqn)
    self._land(env, eqn, eqn.outvars[0], a)


@_register("dot_general")
def _dot_general(self, env, eqn):
    a, b = _in(self, env, eqn)
    (lhs_contract, _rhs_contract), _batch = eqn.params["dimension_numbers"]
    lhs_shape = eqn.invars[0].aval.shape
    k = 1
    for d in lhs_contract:
        k *= lhs_shape[d]
    k = max(k, 1)
    prod = domain.mul(a, b)
    out = Interval(prod.lo * k, prod.hi * k,
                   int_valued=prod.int_valued, reduced=prod.reduced or k > 1)
    if prod.int_valued and k > 1:
        self._note_k_exact(prod.magnitude())
    self._land(env, eqn, eqn.outvars[0], out)


@_register("gather")
def _gather(self, env, eqn):
    operand, indices = _in(self, env, eqn)
    dnums = eqn.params["dimension_numbers"]
    slice_sizes = eqn.params["slice_sizes"]
    op_shape = eqn.invars[0].aval.shape
    self.facts["gathers_checked"] += 1
    ok = True
    mode = eqn.params.get("mode")
    for d in dnums.start_index_map:
        limit = op_shape[d] - slice_sizes[d]
        if indices.lo < 0 or indices.hi > limit:
            ok = False
            self._finding(
                "gather",
                f"gather index envelope [{indices.lo:.6g}, {indices.hi:.6g}] can "
                f"leave [0, {limit}] of operand dim {d} "
                f"(shape {tuple(op_shape)}, slice {tuple(slice_sizes)}, "
                f"mode={mode})",
            )
    if ok:
        self.facts["gathers_proven"] += 1
    self._land(env, eqn, eqn.outvars[0], operand.with_(dominates=frozenset()))


# -- control flow ----------------------------------------------------


@_register("jit", "closed_call", "custom_jvp_call", "custom_vjp_call",
           "custom_vjp_call_jaxpr", "remat", "checkpoint", "core_call")
def _call(self, env, eqn):
    params = eqn.params
    inner = params.get("jaxpr") or params.get("call_jaxpr") or params.get("fun_jaxpr")
    if inner is None:
        self._finding("unknown",
                      f"call primitive {eqn.primitive.name!r} without inner jaxpr")
        for ov in eqn.outvars:
            env[ov] = Interval.of_dtype(ov.aval.dtype)
        return
    args = _in(self, env, eqn)
    # custom_vjp_call carries extra residual-count invars in some
    # versions; trim/extend defensively to the inner arity.
    n_in = len(inner.jaxpr.invars if hasattr(inner, "jaxpr") else inner.invars)
    if len(args) > n_in:
        args = args[len(args) - n_in:]
    outs = self._descend(inner, args, eqn.primitive.name)
    for ov, o in zip(eqn.outvars, outs[len(outs) - len(eqn.outvars):]):
        env[ov] = o


@_register("cond")
def _cond(self, env, eqn):
    index = self._read(env, eqn.invars[0])
    branches = eqn.params["branches"]
    args = [self._read(env, a) for a in eqn.invars[1:]]
    if index.is_point and 0 <= int(index.lo) < len(branches):
        outs = self._descend(branches[int(index.lo)], args,
                             f"cond[{int(index.lo)}]")
    else:
        # Join over all branches.  Ref writes join into shared cells, so
        # running branches sequentially is the join of their effects.
        all_outs = [self._descend(br, args, f"cond[{i}]")
                    for i, br in enumerate(branches)]
        outs = []
        for vals in zip(*all_outs):
            ivs = [v for v in vals if isinstance(v, Interval)]
            outs.append(domain.join_all(ivs) if ivs else vals[0])
    for ov, o in zip(eqn.outvars, outs):
        env[ov] = o


@_register("scan")
def _scan(self, env, eqn):
    p = eqn.params
    body = p["jaxpr"]
    nc, ncarry, length = p["num_consts"], p["num_carry"], p["length"]
    args = _in(self, env, eqn)
    consts, carry, xs = args[:nc], args[nc:nc + ncarry], args[nc + ncarry:]
    steps = min(length, self.policy.scan_cap)
    ys: list[Interval | None] = None
    for i in range(steps):
        outs = self._descend(body, consts + carry + xs, f"scan[{i}]")
        carry = outs[:ncarry]
        step_ys = outs[ncarry:]
        if ys is None:
            ys = list(step_ys)
        else:
            ys = [y.join(s) if isinstance(y, Interval) and isinstance(s, Interval)
                  else s for y, s in zip(ys, step_ys)]
    if length > steps:
        self._finding("note",
                      f"scan of length {length} capped at {steps}; widening carries")
        carry = [Interval.of_dtype(v.aval.dtype)
                 for v in eqn.outvars[:ncarry]]
        ys = [Interval.of_dtype(v.aval.dtype) for v in eqn.outvars[ncarry:]]
    if ys is None:
        ys = [Interval.of_dtype(v.aval.dtype) for v in eqn.outvars[ncarry:]]
    for ov, o in zip(eqn.outvars, list(carry) + list(ys)):
        env[ov] = o


@_register("while")
def _while(self, env, eqn):
    p = eqn.params
    cn, bn = p["cond_nconsts"], p["body_nconsts"]
    body = p["body_jaxpr"]
    args = _in(self, env, eqn)
    body_consts = args[cn:cn + bn]
    carry = args[cn + bn:]
    for _ in range(self.policy.while_cap):
        outs = self._descend(body, body_consts + carry, "while")
        new_carry = [c.join(o) if isinstance(c, Interval) and isinstance(o, Interval)
                     else o for c, o in zip(carry, outs)]
        if all(isinstance(c, Interval) and isinstance(n_, Interval)
               and c.lo == n_.lo and c.hi == n_.hi
               for c, n_ in zip(carry, new_carry)):
            carry = new_carry
            break
        carry = new_carry
    else:
        self._finding("note", "while loop did not stabilize; widening carry")
        carry = [Interval.of_dtype(v.aval.dtype) for v in eqn.outvars]
    for ov, o in zip(eqn.outvars, carry):
        env[ov] = o


# -- pallas ----------------------------------------------------------


@_register("program_id")
def _program_id(self, env, eqn):
    axis = eqn.params["axis"]
    grid_state = getattr(self, "_grid_state", None)
    if grid_state is not None:
        grid, unrolled_axis, step = grid_state
        if axis == unrolled_axis:
            env[eqn.outvars[0]] = Interval.point(float(step))
            return
        hi = max(0, grid[axis] - 1)
        env[eqn.outvars[0]] = Interval(0.0, float(hi), int_valued=True)
        return
    env[eqn.outvars[0]] = Interval(0.0, _INF, int_valued=True)


@_register("num_programs")
def _num_programs(self, env, eqn):
    axis = eqn.params["axis"]
    grid_state = getattr(self, "_grid_state", None)
    if grid_state is not None:
        env[eqn.outvars[0]] = Interval.point(float(grid_state[0][axis]))
    else:
        env[eqn.outvars[0]] = Interval(1.0, _INF, int_valued=True)


@_register("get")
def _get(self, env, eqn):
    cell = env[eqn.invars[0]]
    out = cell.read() if isinstance(cell, _RefCell) else cell
    self._land(env, eqn, eqn.outvars[0], out)


@_register("swap")
def _swap(self, env, eqn):
    cell = env[eqn.invars[0]]
    val = self._read(env, eqn.invars[1])
    if isinstance(cell, _RefCell):
        old = cell.read()
        cell.write(val)
    else:
        old = cell
    env[eqn.outvars[0]] = old


@_register("addupdate")
def _addupdate(self, env, eqn):
    cell = env[eqn.invars[0]]
    val = self._read(env, eqn.invars[1])
    if isinstance(cell, _RefCell):
        cell.write(domain.add(cell.read(), val))


@_register("pallas_call")
def _pallas_call(self, env, eqn):
    gm = eqn.params["grid_mapping"]
    kernel = eqn.params["jaxpr"]
    grid = tuple(gm.grid)
    n_in, n_out = gm.num_inputs, gm.num_outputs
    args = _in(self, env, eqn)
    invars = kernel.invars
    # kernel invars: [index operands][input refs][output refs][scratch]
    n_scratch = getattr(gm, "num_scratch_operands", 0)
    n_index = max(len(invars) - n_in - n_out - n_scratch, 0)
    bindings: list[Any] = []
    ai = 0
    for _ in range(n_index):
        bindings.append(args[ai] if ai < len(args) else Interval(0.0, _INF, int_valued=True))
        ai += 1
    in_cells = []
    for v in invars[n_index:n_index + n_in]:
        iv = args[ai] if ai < len(args) else Interval.of_dtype(v.aval.dtype)
        ai += 1
        cell = _RefCell(v.aval.dtype, iv)
        in_cells.append(cell)
        bindings.append(cell)
    out_cells = [_RefCell(v.aval.dtype) for v in invars[n_index + n_in:
                                                        n_index + n_in + n_out]]
    bindings.extend(out_cells)
    for v in invars[n_index + n_in + n_out:]:
        bindings.append(_RefCell(v.aval.dtype))

    # Unroll the innermost grid axis (the K/revisit axis in every GEMM
    # kernel here) with a precise program_id so k==0 init branches
    # resolve exactly; other axes stay symbolic.
    steps = grid[-1] if grid else 1
    capped = steps > self.policy.grid_cap
    if capped:
        self._finding("note",
                      f"grid axis of size {steps} capped at {self.policy.grid_cap}")
        steps = self.policy.grid_cap
    prev_grid_state = getattr(self, "_grid_state", None)
    name = eqn.params.get("name", "kernel")
    try:
        for step in range(max(steps, 1)):
            self._grid_state = (grid, len(grid) - 1, step) if grid else None
            self.stack.append(f"pallas_call:{name}[k={step}]")
            try:
                self.run(kernel, [], list(bindings))
            finally:
                self.stack.pop()
    finally:
        self._grid_state = prev_grid_state
    for ov, cell in zip(eqn.outvars, out_cells):
        env[ov] = cell.read()


# prngs / misc: carrier-range results
@_register("random_seed", "random_wrap", "random_bits", "random_unwrap",
           "random_fold_in", "threefry2x32", "random_gamma")
def _random(self, env, eqn):
    for ov in eqn.outvars:
        dtype = getattr(ov.aval, "dtype", None)
        try:
            env[ov] = (Interval.of_dtype(dtype) if dtype is not None
                       else Interval(-_INF, _INF))
        except TypeError:  # opaque dtypes (PRNG key<fry>) have no bounds
            env[ov] = Interval(-_INF, _INF)
