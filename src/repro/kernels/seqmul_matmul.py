"""Pallas TPU kernel: fused approximate GEMM with the splitting point ``t``
*inside* the tile loop.

This is the paper's segmented-carry sequential multiplier deployed as a
blocked GEMM instead of an elementwise post-pass.  Historically a
"seqmul" matmul meant: flatten the (M, K, N) outer-product pairs, run the
elementwise kernel (`kernels.seqmul_kernel`) over O(M·K·N) words in HBM,
then reduce — the recurrence was an *outer loop around* generic kernels
and the intermediate product tensor round-tripped through HBM.

Here the grid is the classic (M/BM, N/BN, K/BK) reduction layout with the
K axis innermost and the f32 accumulator tile resident in VMEM (init at
k==0, accumulate after).  Each grid step broadcasts its (BM, BK) × (BK, BN)
magnitude tiles to a (BM, BK, BN) cube *in VMEM*, runs the n-cycle
split-word recurrence from `repro.engine.recurrence` — the same single
body the jnp reference and the elementwise kernel use, so bit-exactness
is structural — assembles product values in f32, applies the
sign-magnitude rank-1 sign product, and reduces over the tile's K extent
into the accumulator.  Nothing of O(M·K·N) ever exists outside VMEM.

Accumulations are exact: products are integers < 2^{2n} and partial sums
stay integer-valued in f32 for n <= 12 and K within the tested range
(|sum| < 2^24), so the tile reduction order cannot perturb the result —
asserted against the reference oracle in ``tests/test_fused_kernels.py``.

VMEM budget: the recurrence keeps ~8 live uint32 cubes of shape
(BM, BK, BN); the default (8, 128, 128) tiles put that at ~4 MiB, under
the ~16 MiB/core budget (see docs/kernels.md for the sizing table).  Tile
sizes are resolved per call by ``engine.config.kernel_tiles`` so quality
tiers can trade tile footprint against grid overhead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.engine.policy import resolve_interpret
from repro.engine.recurrence import seqmul_recurrence, validate_nt
from repro.kernels import row_block

__all__ = ["seqmul_matmul_pallas", "DEFAULT_BM", "DEFAULT_BN", "DEFAULT_BK"]

# (8, 128, 128) u32 cube = 512 KiB per live recurrence word (~8 live):
# inside VMEM, with K and N extents that are lane multiples as the chip's
# compiler requires of a block's last dimension.
DEFAULT_BM = 8
DEFAULT_BN = 128
DEFAULT_BK = 128


def _kernel(ma_ref, sa_ref, mb_ref, sb_ref, o_ref, *, n, t, approx, fix_to_1):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ma = ma_ref[...]  # (BM, BK) uint32 magnitudes
    mb = mb_ref[...]  # (BK, BN)
    bm, bk = ma.shape
    bn = mb.shape[1]
    # The splitting point t lives HERE: the n-cycle segmented-carry
    # recurrence runs on the (BM, BK, BN) outer-product cube in VMEM.
    a3 = jnp.broadcast_to(ma[:, :, None], (bm, bk, bn))
    b3 = jnp.broadcast_to(mb[None, :, :], (bm, bk, bn))
    lo, s_lsp, s_msp, _ = seqmul_recurrence(
        a3, b3, n=n, t=t, approx=approx, fix_to_1=fix_to_1
    )
    # assemble the 2n-bit product value in f32 (exact for n <= 12); the
    # words go through int32 first — they are below 2^24, and Mosaic has
    # no uint32 -> float32 conversion
    def f32(x):
        return x.astype(jnp.int32).astype(jnp.float32)

    prod = f32(lo) + jnp.float32(1 << (n - 1)) * (
        f32(s_lsp) + jnp.float32(1 << t) * f32(s_msp)
    )
    signs = sa_ref[...][:, :, None] * sb_ref[...][None, :, :]
    o_ref[...] += (prod * signs).sum(axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("n", "t", "approx", "fix_to_1", "bm", "bn", "bk", "interpret"),
)
def _seqmul_matmul_jit(
    mag_a: jax.Array,
    sign_a: jax.Array,
    mag_b: jax.Array,
    sign_b: jax.Array,
    *,
    n: int,
    t: int,
    approx: bool,
    fix_to_1: bool,
    bm: int,
    bn: int,
    bk: int,
    interpret: bool,
) -> jax.Array:
    m_dim, k_dim = mag_a.shape
    k2, n_dim = mag_b.shape
    assert k_dim == k2, (mag_a.shape, mag_b.shape)
    bm = row_block(bm, m_dim)

    def pad2(x, r, c, dt):
        x = jnp.asarray(x, dt)
        return jnp.pad(x, ((0, -x.shape[0] % r), (0, -x.shape[1] % c)))

    # zero-magnitude / zero-sign padding contributes exactly 0 to every
    # accumulator cell (0·0 never produces an LSP carry, so fix-to-1
    # cannot fire on pad lanes)
    ma = pad2(mag_a, bm, bk, jnp.uint32)
    sa = pad2(sign_a, bm, bk, jnp.float32)
    mb = pad2(mag_b, bk, bn, jnp.uint32)
    sb = pad2(sign_b, bk, bn, jnp.float32)
    mp, kp, np_ = ma.shape[0], ma.shape[1], mb.shape[1]

    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, t=t, approx=approx, fix_to_1=fix_to_1),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(ma, sa, mb, sb)
    return out[:m_dim, :n_dim]


def seqmul_matmul_pallas(
    mag_a: jax.Array,
    sign_a: jax.Array,
    mag_b: jax.Array,
    sign_b: jax.Array,
    *,
    n: int,
    t: int,
    approx: bool = True,
    fix_to_1: bool = True,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool | None = None,
) -> jax.Array:
    """(M, K) x (K, N) -> (M, N) f32 approximate GEMM, recurrence in-tile.

    mag_*: uint32 magnitudes in [0, 2^n); sign_*: f32/int8 in {-1, 0, 1}.
    ``interpret=None`` resolves through the engine's shared backend policy.
    """
    validate_nt(n, t)
    if n > 12:
        raise ValueError(
            f"seqmul_matmul_pallas accumulates assembled products in f32, "
            f"exact only for n <= 12 (got n={n}); use the elementwise "
            f"two-word path (kernels.seqmul_kernel.seqmul_pallas_words) "
            f"for wider operands"
        )
    return _seqmul_matmul_jit(
        mag_a, sign_a, mag_b, sign_b,
        n=n, t=t, approx=approx, fix_to_1=fix_to_1,
        bm=bm, bn=bn, bk=bk, interpret=resolve_interpret(interpret),
    )


def audit_trace(*, n: int, t: int, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                bk: int = DEFAULT_BK):
    """Static-audit contract for the fused seqmul GEMM (no execution).

    Traces ``_seqmul_matmul_jit`` directly — *bypassing* the public
    ``n <= 12`` guard — under the documented input contract (magnitudes
    in ``[0, 2^n - 1]``, signs in {-1, 0, 1}), so the f32-exactness
    bound is rediscovered by ``repro.analysis`` as a derived fact
    rather than assumed from this module's docstring.
    """
    from repro.analysis.spec import TraceSpec, ValueRange, sds

    fn = functools.partial(
        _seqmul_matmul_jit, n=n, t=t, approx=True, fix_to_1=True,
        bm=bm, bn=bn, bk=bk, interpret=True,
    )
    q, s = ValueRange.quantized(n), ValueRange.sign()
    m_dim, k_dim, n_dim = bm, 2 * bk, bn
    return TraceSpec(
        name=f"kernel:seqmul_matmul[n={n},t={t}]",
        fn=fn,
        args=[sds((m_dim, k_dim), jnp.uint32), sds((m_dim, k_dim), jnp.float32),
              sds((k_dim, n_dim), jnp.uint32), sds((k_dim, n_dim), jnp.float32)],
        ranges=[q, s, q, s],
        exact_products=True,
    )
