"""The main-path GEMM kernels compile natively for a TPU v5e chip.

Interpret mode cannot show the chip's compiler refusing a block that is
out of the (8, 128) tiling or a lowering Mosaic does not have.  These
tests compile each fused GEMM kernel with ``interpret=False`` for a
described (not attached) ``v5e:2x2`` topology, at qwen3-0.6b's MLP shapes
with the tiles ``engine.config.kernel_tiles`` hands out, and read the
compiled program's memory analysis.  Nothing runs, so they say nothing
about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.engine import config as engine_config

ROWS = (8, 128)  # a decode batch and a prompt bucket
MLP_SHAPES = ((1024, 3072), (3072, 1024))  # (K, N) of w1/w3 and w2
N_BITS, T_SPLIT, RANK = 8, 4, 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _operands(kernel, m, k, n):
    """(jitted kernel body with its static arguments, operand shapes)."""
    from repro.kernels import lowrank_matmul, lut_matmul, packed_matmul, seqmul_matmul

    mode = {"lut": "bitexact", "seqmul": "seqmul", "packed": "inject",
            "lowrank": "lowrank"}[kernel]
    tiles = engine_config.kernel_tiles(mode, N_BITS, T_SPLIT)
    kw = dict(bm=tiles.bm, bn=tiles.bn, bk=tiles.bk, interpret=False)
    u32, f32 = jnp.uint32, jnp.float32
    if kernel == "lut":
        fn = functools.partial(lut_matmul._lut_matmul_jit, n=N_BITS, **kw)
        shapes = [((1 << 2 * N_BITS,), jnp.int32), ((m, k), u32), ((m, k), f32),
                  ((k, n), u32), ((k, n), f32)]
    elif kernel == "seqmul":
        fn = functools.partial(seqmul_matmul._seqmul_matmul_jit, n=N_BITS, t=T_SPLIT,
                               approx=True, fix_to_1=True, **kw)
        shapes = [((m, k), u32), ((m, k), f32), ((k, n), u32), ((k, n), f32)]
    elif kernel == "packed":
        fn = functools.partial(packed_matmul._packed_matmul_jit, **kw)
        shapes = [((m, k // 2), u32), ((k // 2, n), u32)]
    else:
        fn = functools.partial(lowrank_matmul._lowrank_matmul_jit, rank=RANK, **kw)
        shapes = [((m, k), f32), ((k, n), f32), ((m, k, RANK), f32), ((k, n, RANK), f32)]
    return fn, shapes


@pytest.mark.parametrize("shape", MLP_SHAPES, ids=lambda s: f"K{s[0]}xN{s[1]}")
@pytest.mark.parametrize("m", ROWS, ids=lambda m: f"M{m}")
@pytest.mark.parametrize("kernel", ["lut", "seqmul", "packed", "lowrank"])
def test_kernel_compiles_for_v5e(one_chip, kernel, m, shape):
    k, n = shape
    fn, shapes = _operands(kernel, m, k, n)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "the Pallas kernel was not lowered"
    mem = compiled.memory_analysis()
    out_bytes = m * n * 4
    assert mem.output_size_in_bytes == out_bytes
    # padding and operand re-layout stay small beside the operands
    assert mem.temp_size_in_bytes <= 4 * mem.argument_size_in_bytes


# The benchmark's configurations at their cells' pool shapes: (registry
# name, overrides, rows); every row holds a 128-token prompt bucket and
# 256 outputs.
DECODE_CELLS = {
    "qwen3-0.6b": ("qwen3-0.6b", {}, 32),
    "yi-9b-24l": ("yi-9b", {"num_layers": 24}, 16),
}
POOL_SLOTS = 384


@pytest.mark.parametrize("cell", list(DECODE_CELLS))
def test_decode_step_reads_pool_in_place(one_chip, cell):
    """The exact tier's decode step, compiled for the chip with the pool
    donated, neither copies the stacked pool nor repeats it to the query
    heads, has no loop but the layer scan, and needs temporaries of
    under a tenth of the pool."""
    import re

    from repro.configs.registry import get_config
    from repro.models.registry import build_model
    from repro.serve.scheduler import _apply_pool_quality, _scatter_row
    from repro.serve.strategy import build_tier_engine

    arch, over, rows = DECODE_CELLS[cell]
    model, tier = _apply_pool_quality(build_model(get_config(arch, **over)), "exact")
    cfg = model.cfg
    engine = build_tier_engine(model, POOL_SLOTS, name=tier, key=tier,
                               scatter_row=_scatter_row)

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    params = abstract(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    pool = abstract(jax.eval_shape(
        lambda: model.init_caches(rows, POOL_SLOTS, jnp.dtype(cfg.dtype))))
    tok = jax.ShapeDtypeStruct((rows, 1), jnp.int32, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    compiled = engine.decode.lower(params, pool, tok, vec, vec).compile()
    hlo = compiled.as_text()

    kv, hd = cfg.num_kv_heads, cfg.head_dim
    stacked = f"[{cfg.num_layers},{rows},{POOL_SLOTS},{kv},{hd}]"
    copies = re.findall(rf"= \w+{re.escape(stacked)}\S* copy\(", hlo)
    assert not copies, f"the stacked pool is copied: {copies}"
    repeated = f"[{rows},{POOL_SLOTS},{kv},{cfg.num_heads // kv},{hd}]"
    repeats = re.findall(rf"= \w+{re.escape(repeated)}\S* broadcast\(", hlo)
    assert not repeats, f"the cache is repeated to the query heads: {repeats}"
    assert len(re.findall(r" while\(", hlo)) == 1, "a loop besides the layer scan"
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(pool))
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 10


def test_falcon_h1_stage_fits_one_chip(one_chip):
    """falcon-h1-34b-8l's exact-tier steps at its cell's pool (32 slots of
    128 + 256), compiled for the chip: the decode step, a batch-1
    admission and the pool prefill each fit one v5e's 16 GiB with their
    arguments, outputs not aliased to them and temporaries; the decode step
    writes its pool in place and copies no stacked KV pool."""
    import re

    from repro.configs.registry import get_config
    from repro.models.registry import build_model
    from repro.serve.scheduler import _apply_pool_quality, _scatter_row
    from repro.serve.strategy import build_tier_engine

    rows, bucket = 32, 128
    model, tier = _apply_pool_quality(
        build_model(get_config("falcon-h1-34b", num_layers=8)), "exact")
    cfg = model.cfg
    engine = build_tier_engine(model, POOL_SLOTS, name=tier, key=tier,
                               scatter_row=_scatter_row)

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = abstract(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    pool = abstract(jax.eval_shape(
        lambda: model.init_caches(rows, POOL_SLOTS, jnp.dtype(cfg.dtype))))
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(pool))
    # 32 x 8 x (32 heads x 128 x 256 f32 state + 3 x 5120 bf16 conv inputs)
    assert model.state_bytes(pool) == 32 * 8 * (32 * 128 * 256 * 4 + 3 * 5120 * 2)
    steps = {
        "decode": engine.decode.lower(params, pool, ints(rows, 1), ints(rows), ints(rows)),
        "admit": engine.admit_step.lower(params, pool, ints(1, bucket), ints(1, bucket),
                                         ints()),
        "pool_prefill": engine.prefill_pool.lower(params, ints(rows, bucket),
                                                  ints(rows, bucket)),
    }
    hbm = 16 * 2 ** 30
    compiled = {name: lowered.compile() for name, lowered in steps.items()}
    for name, step in compiled.items():
        mem = step.memory_analysis()
        held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        # the weights are 12.23 GB; what is left must hold the pool and
        # the step's temporaries with room for the runtime's own
        assert held < 0.92 * hbm, f"{name}: {held / 1e9:.2f} GB"
        if name != "pool_prefill":
            assert mem.alias_size_in_bytes == pool_bytes, f"{name} does not write the pool in place"
    hlo = compiled["decode"].as_text()
    stacked = f"[{cfg.num_layers},{rows},{POOL_SLOTS},{cfg.num_kv_heads},{cfg.head_dim}]"
    assert not re.findall(rf"= \w+{re.escape(stacked)}\S* copy\(", hlo), "the KV pool is copied"
