"""Pallas TPU kernel: approximate GEMM via a VMEM-resident product LUT.

TPU adaptation of the paper's LUT-fabric deployment: every scalar product
``lut[|a|, |b|]`` of the (2^n, 2^n) approximate-product table is selected
on the MXU instead of gathered on the VPU (Mosaic lowers only
same-shape 2-D gathers, not a table lookup).  The table is split into
two byte planes, ``lut = 256 * hi + lo`` with ``hi, lo`` in [0, 255] —
both exact in bf16 — and pinned in VMEM side by side as one
``(W, 2W)`` bf16 operand (``W = max(2^n, 128)``, zero-padded to MXU
width).  For each K index ``k`` of a grid step's tile the kernel

1. builds the signed one-hot rows of A's column ``k``,
   ``oh[m, j] = s_a[m, k] * [|a[m, k]| == j]``, and selects the table
   rows with one MXU dot: ``u = oh @ [hi | lo]`` is
   ``s_a * lut[|a|, :]`` split into its two bytes, exact in f32;
2. builds the signed one-hot columns of B's row ``k``,
   ``v[j, n] = s_b[k, n] * [|b[k, n]| == j]``, and contracts the stacked
   byte rows ``[u_hi; u_lo]`` with it — one more MXU dot picks
   ``s_a s_b lut[|a|, |b|]`` for every (m, n).

The K loop inside a tile is unrolled: a column of A is a lane of its
block, and the chip's compiler slices lanes only at static offsets.
The two byte planes accumulate separately in the revisited output block
(init at k == 0): each sum is an integer below ``K * 255``, exact in
f32, and the planes are combined once, ``256 * hi + lo``, after the
kernel.  Every product is therefore the table entry itself, bit for bit
(``engine.modes.bitexact_gemm_int``), and the only rounding is that of
the final combine — none at all while ``|sum| < 2^24``.

Grid is (M/BM, N/BN, K/BK) with the K axis innermost.  ``bm`` is
clamped by :func:`repro.kernels.row_block`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.engine.policy import resolve_interpret
from repro.kernels import row_block

__all__ = ["lut_matmul_pallas", "table_planes", "DEFAULT_BM", "DEFAULT_BN", "DEFAULT_BK"]

DEFAULT_BM = 128
DEFAULT_BN = 256
DEFAULT_BK = 128

_LANE = 128


def table_width(n: int) -> int:
    """Padded one-hot width of an n-bit table: 2^n, at least one lane tile."""
    return max(1 << n, _LANE)


def table_planes(lut: jax.Array, n: int) -> jax.Array:
    """(2^n, 2^n) int32 product table -> (W, 2W) bf16 byte planes [hi | lo].

    Entries are below 2^16 for n <= 8, so both bytes are integers in
    [0, 255] and exact in bf16.  Rows and columns past 2^n are zero and
    are never selected (magnitudes are clamped to 2^n - 1)."""
    w = table_width(n)
    q = 1 << n
    tab = jnp.pad(jnp.asarray(lut, jnp.int32).reshape(q, q), ((0, w - q), (0, w - q)))
    hi = jnp.right_shift(tab, 8)
    lo = jnp.bitwise_and(tab, 0xFF)
    return jnp.concatenate([hi, lo], axis=1).astype(jnp.bfloat16)


def _kernel(tab_ref, ma_ref, sa_ref, mb_ref, sb_ref, o_ref, *, w: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bm, bk = ma_ref.shape
    bn = mb_ref.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, w), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (w, bn), 0)
    tab = tab_ref[...]
    ma, sa = ma_ref[...], sa_ref[...]
    mb, sb = mb_ref[...], sb_ref[...]
    acc = o_ref[...]
    for kk in range(bk):
        # signed one-hot rows of A's column kk select s_a * lut[|a|, :] as
        # its two byte planes; stacked, they meet B's signed one-hot row kk
        oh = jnp.where(ma[:, kk:kk + 1] == cols, sa[:, kk:kk + 1], 0.0)
        u = jnp.dot(oh.astype(jnp.bfloat16), tab, preferred_element_type=jnp.float32)
        lhs = jnp.concatenate([u[:, :w], u[:, w:]], axis=0).astype(jnp.bfloat16)
        v = jnp.where(mb[kk:kk + 1, :] == rows, sb[kk:kk + 1, :], 0.0)
        acc += jnp.dot(lhs, v.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    o_ref[...] = acc


@functools.partial(
    jax.jit, static_argnames=("n", "bm", "bn", "bk", "interpret")
)
def _lut_matmul_jit(
    lut: jax.Array,
    mag_a: jax.Array,
    sign_a: jax.Array,
    mag_b: jax.Array,
    sign_b: jax.Array,
    *,
    n: int,
    bm: int,
    bn: int,
    bk: int,
    interpret: bool,
) -> jax.Array:
    m_dim, k_dim = mag_a.shape
    k2, n_dim = mag_b.shape
    assert k_dim == k2, (mag_a.shape, mag_b.shape)
    bm = row_block(bm, m_dim)
    w = table_width(n)
    tab = table_planes(lut, n)

    # Clamp magnitudes into the table's [0, 2^n) domain: an out-of-range
    # quantized magnitude (buggy upstream calibration, adversarial
    # operands) saturates to the table edge instead of selecting nothing.
    qmax = jnp.uint32((1 << n) - 1)

    def clamp(x):
        return jnp.minimum(jnp.asarray(x, jnp.uint32), qmax).astype(jnp.int32)

    def pad2(x, r, c, dt):
        x = jnp.asarray(x, dt)
        return jnp.pad(x, ((0, -x.shape[0] % r), (0, -x.shape[1] % c)))

    # zero-magnitude / zero-sign padding selects a zero product
    ma = pad2(clamp(mag_a), bm, bk, jnp.int32)
    sa = pad2(sign_a, bm, bk, jnp.float32)
    mb = pad2(clamp(mag_b), bk, bn, jnp.int32)
    sb = pad2(sign_b, bk, bn, jnp.float32)
    mp, kp, np_ = ma.shape[0], ma.shape[1], mb.shape[1]

    grid = (mp // bm, np_ // bn, kp // bk)
    planes = pl.pallas_call(
        functools.partial(_kernel, w=w),
        grid=grid,
        in_specs=[
            pl.BlockSpec((w, 2 * w), lambda i, j, k: (0, 0)),  # table: whole
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        # per row block: bm rows of high-byte sums, then bm of low-byte sums
        out_specs=pl.BlockSpec((2 * bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((2 * mp, np_), jnp.float32),
        interpret=interpret,
    )(tab, ma, sa, mb, sb)
    planes = planes.reshape(mp // bm, 2, bm, np_)
    out = (planes[:, 0] * 256.0 + planes[:, 1]).reshape(mp, np_)
    return out[:m_dim, :n_dim]


def lut_matmul_pallas(
    lut: jax.Array,
    mag_a: jax.Array,
    sign_a: jax.Array,
    mag_b: jax.Array,
    sign_b: jax.Array,
    *,
    n: int = 8,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool | None = None,
) -> jax.Array:
    """(M, K) x (K, N) -> (M, N) f32 approximate GEMM.

    lut: (2^n * 2^n,) or (2^n, 2^n) int32 product table, n <= 8.
    mag_*: uint32 magnitudes in [0, 2^n); sign_*: f32/int8 in {-1, 0, 1}.
    ``interpret=None`` resolves through the engine's shared backend policy.
    """
    if n > 8:
        raise ValueError(f"lut_matmul_pallas splits table entries into two bytes, "
                         f"which holds products of n <= 8 bits (got n={n})")
    return _lut_matmul_jit(
        lut, mag_a, sign_a, mag_b, sign_b,
        n=n, bm=bm, bn=bn, bk=bk, interpret=resolve_interpret(interpret),
    )


def audit_trace(*, n: int, t: int = 0, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                bk: int = DEFAULT_BK, mag_slack_bits: int = 2):
    """Static-audit contract for the LUT GEMM (no execution).

    The magnitude contract is deliberately *adversarial*: inputs range
    over ``[0, 2^{n + mag_slack_bits} - 1]`` — a miscalibrated upstream
    quantizer — so what ``repro.analysis`` proves is that the clamp and
    the byte split keep every one-hot selection and every byte sum
    inside its carrier even for out-of-contract magnitudes.  (``t`` only
    shapes the table contents, not the dataflow; accepted for interface
    uniformity.)
    """
    del t
    from repro.analysis.spec import TraceSpec, ValueRange, sds

    fn = functools.partial(_lut_matmul_jit, n=n, bm=bm, bn=bn, bk=bk,
                           interpret=True)
    mag = ValueRange(0.0, float((1 << (n + mag_slack_bits)) - 1), int_valued=True)
    sgn = ValueRange.sign()
    # table entries are 2n-bit approximate products
    lut_vals = ValueRange(0.0, float((1 << (2 * n)) - 1), int_valued=True)
    m_dim, k_dim, n_dim = bm, 2 * bk, bn
    return TraceSpec(
        name=f"kernel:lut_matmul[n={n}]",
        fn=fn,
        args=[sds(((1 << n) * (1 << n),), jnp.int32),
              sds((m_dim, k_dim), jnp.uint32), sds((m_dim, k_dim), jnp.float32),
              sds((k_dim, n_dim), jnp.uint32), sds((k_dim, n_dim), jnp.float32)],
        ranges=[lut_vals, mag, sgn, mag, sgn],
        exact_products=True,
    )
