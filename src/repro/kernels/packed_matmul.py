"""Pallas TPU kernel: int-packed GEMM — two quantized lanes per uint32.

The cheap quality tiers must move *fewer bytes*, not just spend fewer
abstract gate delays (the energy/latency framing of the approximate-
multiplier literature).  This kernel is the ``draft``-tier fast path:
both operands are absmax-quantized to signed n-bit integers (n <= 15,
i.e. int16 lanes), packed two-consecutive-K-values per uint32 on the
host side, and streamed through the (M/BM, N/BN, K'/BK') reduction grid
at **half the HBM bytes of the f32 operands** (K' = K/2 packed words).

Inside the kernel each packed tile is bitcast to int32 and split into
its even/odd int16 lanes with arithmetic shifts; the contraction is two
MXU dots (even-lane plane + odd-lane plane) into the VMEM-resident f32
accumulator:

    acc += a_even @ b_even + a_odd @ b_odd      == qa @ qb  (exact)

Quantized values are integers |q| < 2^n, so the f32 accumulation is
exact for n <= 11 over the benchmarked K range — the packed path
bit-matches the unpacked quantized GEMM, asserted in
``tests/test_fused_kernels.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.engine.policy import resolve_interpret
from repro.kernels import row_block

__all__ = ["pack_i16_pairs", "packed_matmul_pallas", "DEFAULT_BM", "DEFAULT_BN", "DEFAULT_BK"]

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128  # packed words: 128 u32 = 256 int16 K-lanes per tile


def pack_i16_pairs(q: jax.Array, *, axis: int) -> jax.Array:
    """Pack consecutive pairs along ``axis`` of a signed-int array into
    uint32 words (low half = even index, high half = odd index).  Pads the
    axis to even length with zeros; values must fit int16."""
    q = jnp.asarray(q, jnp.int32)
    if q.shape[axis] % 2:
        pad = [(0, 0)] * q.ndim
        pad[axis] = (0, 1)
        q = jnp.pad(q, pad)
    even = jax.lax.slice_in_dim(q, 0, q.shape[axis], stride=2, axis=axis)
    odd = jax.lax.slice_in_dim(q, 1, q.shape[axis], stride=2, axis=axis)
    word = (even & jnp.int32(0xFFFF)) | (odd << 16)
    return jax.lax.bitcast_convert_type(word, jnp.uint32)


def _unpack(tile: jax.Array) -> tuple[jax.Array, jax.Array]:
    """uint32 tile -> (even, odd) f32 lanes via sign-extending shifts."""
    w = jax.lax.bitcast_convert_type(tile, jnp.int32)
    even = jax.lax.shift_right_arithmetic(jax.lax.shift_left(w, 16), 16)
    odd = jax.lax.shift_right_arithmetic(w, 16)
    return even.astype(jnp.float32), odd.astype(jnp.float32)


def _kernel(pa_ref, pb_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a_even, a_odd = _unpack(pa_ref[...])  # (BM, BK') each
    b_even, b_odd = _unpack(pb_ref[...])  # (BK', BN) each
    acc = jnp.dot(a_even, b_even, preferred_element_type=jnp.float32)
    acc += jnp.dot(a_odd, b_odd, preferred_element_type=jnp.float32)
    o_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _packed_matmul_jit(
    pa: jax.Array,  # (M, K') uint32 — packed along K
    pb: jax.Array,  # (K', N) uint32
    *,
    bm: int,
    bn: int,
    bk: int,
    interpret: bool,
) -> jax.Array:
    m_dim, kp_dim = pa.shape
    kp2, n_dim = pb.shape
    assert kp_dim == kp2, (pa.shape, pb.shape)
    bm = row_block(bm, m_dim)

    def pad2(x, r, c):
        return jnp.pad(jnp.asarray(x, jnp.uint32), ((0, -x.shape[0] % r), (0, -x.shape[1] % c)))

    ap = pad2(pa, bm, bk)
    bp = pad2(pb, bk, bn)
    mp, kp, np_ = ap.shape[0], ap.shape[1], bp.shape[1]

    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(ap, bp)
    return out[:m_dim, :n_dim]


def packed_matmul_pallas(
    pa: jax.Array,
    pb: jax.Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool | None = None,
) -> jax.Array:
    """Packed (M, K/2) x (K/2, N) -> (M, N) f32 integer GEMM.

    Operands come from :func:`pack_i16_pairs` along the contraction axis
    (axis=1 for the left operand, axis=0 for the right).  ``interpret=None``
    resolves through the engine's shared backend policy.
    """
    return _packed_matmul_jit(
        pa, pb, bm=bm, bn=bn, bk=bk, interpret=resolve_interpret(interpret)
    )


def audit_trace(*, n: int = 15, t: int = 0, bm: int = DEFAULT_BM,
                bn: int = DEFAULT_BN, bk: int = DEFAULT_BK):
    """Static-audit contract for the packed GEMM (no execution).

    Operands are arbitrary uint32 words (any int16 lane pattern): the
    audit proves the sign-extending lane extraction and the two-plane
    contraction never overflow their carriers.  Lane *value* bounds are
    erased by the bit-packing, so f32-exactness of the products is a
    runtime parity property (tests), not a static one — the trace runs
    with ``exact_products=False``.
    """
    del n, t
    from repro.analysis.spec import TraceSpec, sds

    fn = functools.partial(_packed_matmul_jit, bm=bm, bn=bn, bk=bk,
                           interpret=True)
    return TraceSpec(
        name="kernel:packed_matmul",
        fn=fn,
        args=[sds((bm, 2 * bk), jnp.uint32), sds((2 * bk, bn), jnp.uint32)],
        exact_products=False,
    )
