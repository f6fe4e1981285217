"""Sharding rules: parameters, activations, caches.

Conventions (DESIGN.md §4):
  mesh axes   ("pod", "data", "model") multi-pod / ("data", "model") pod
  DP          batch over ("pod", "data")
  TP          heads / d_ff / vocab / experts over "model"
  FSDP        the largest remaining param dim over "data"

Every rule degrades gracefully: an axis is only assigned if the dimension
is divisible by the mesh extent (e.g. granite's vocab 49155 is not 16-
divisible -> falls back to the next candidate or replication).  Constraints
are no-ops outside a mesh context, so the same model code runs on one CPU
device and on the 512-chip production mesh.
"""

from __future__ import annotations

import re
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

DP = ("pod", "data")  # logical data-parallel axes (present subset is used)
TP = "model"
FSDP = "data"

__all__ = [
    "DP", "TP", "FSDP", "ambient_mesh", "mesh_context", "make_auto_mesh",
    "data_parallel_mesh", "shard_map", "constrain", "param_spec",
    "param_specs", "mesh_axis_sizes",
]


def ambient_mesh():
    """The mesh installed by :func:`mesh_context` around the current
    trace, or None.  Every rule in this module is a no-op when this
    returns None, so the same model code runs on one device and on a
    mesh."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def mesh_context(mesh):
    """Context manager installing ``mesh`` for the duration of a trace."""
    return jax.sharding.set_mesh(mesh)


def make_auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with every axis of type Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def data_parallel_mesh(batch_size: Optional[int] = None, *, devices=None):
    """A 1-D ``("data",)`` serving mesh over the available devices, or None.

    Picks the largest device count that divides ``batch_size`` (all of
    them when ``batch_size`` is None), so installing the result around a
    decode loop shards the request batch over data via the model's
    ambient ``constrain`` rules.  Returns None on a single device (or
    when nothing divides) — serving then runs unsharded, no mesh context
    needed.  This is the ``distributed`` half of the continuous-batching
    scheduler's optional data-parallel decode (docs/serving.md).
    """
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    if batch_size is not None:
        while n > 1 and batch_size % n:
            n -= 1
    if n <= 1:
        return None
    import numpy as np

    return jax.sharding.Mesh(np.array(devs[:n]), ("data",))


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def mesh_axis_sizes(mesh=None) -> dict:
    m = mesh or ambient_mesh()
    if m is None:
        return {}
    return dict(zip(m.axis_names, m.axis_sizes))


def _resolve_entry(entry, dim: int, sizes: dict) -> Optional[object]:
    """Keep only mesh-present axes; drop the entry unless dim divides."""
    if entry is None:
        return None
    axes = entry if isinstance(entry, tuple) else (entry,)
    axes = tuple(a for a in axes if a in sizes)
    if not axes:
        return None
    total = 1
    for a in axes:
        total *= sizes[a]
    if dim % total != 0:
        # try a shrinking prefix (e.g. ("pod","data") -> ("pod",))
        for k in range(len(axes) - 1, 0, -1):
            tot = 1
            for a in axes[:k]:
                tot *= sizes[a]
            if dim % tot == 0:
                return axes[:k] if k > 1 else axes[0]
        return None
    return axes if len(axes) > 1 else axes[0]


def resolve_spec(spec: tuple, shape: tuple, sizes: dict) -> P:
    assert len(spec) == len(shape), (spec, shape)
    return P(*[_resolve_entry(e, d, sizes) for e, d in zip(spec, shape)])


def constrain(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint that adapts to (or skips without) the mesh."""
    m = ambient_mesh()
    if m is None:
        return x
    sizes = mesh_axis_sizes(m)
    return jax.lax.with_sharding_constraint(x, resolve_spec(tuple(spec), x.shape, sizes))


# ---------------------------------------------------------------------------
# Parameter sharding rules, by parameter-tree path (joined with '/').
# Trailing-dims spec; leading (scan-group) dims are padded with None.
# Order matters: first match wins.
_RULES: list[tuple[str, tuple]] = [
    (r"embed", (TP, FSDP)),  # (vocab, d_model)
    (r"lm_head", (FSDP, TP)),  # (d_model, vocab)
    (r"(wq|wk|wv)$", (FSDP, TP)),  # (d_model, heads*hd)
    (r"wo$", (TP, FSDP)),  # (heads*hd, d_model)
    (r"(w1|w3)$", (FSDP, TP)),  # (d_model, d_ff)
    (r"w2$", (TP, FSDP)),  # (d_ff, d_model)
    (r"router", (FSDP, None)),  # (d_model, experts)
    (r"(we1|we3)$", (TP, FSDP, None)),  # (experts, d_model, ff)
    (r"we2$", (TP, None, FSDP)),  # (experts, ff, d_model)
    (r"(in_proj|gate_proj|x_proj)$", (FSDP, TP)),
    (r"out_proj$", (TP, FSDP)),
    (r"conv_w$", (None, TP)),  # (conv_width, channels)
    (r"(lru_a|lru_gate_w|lru_gate_b|conv_b)", None),  # small recurrent params
    (r"(ssm_a|ssm_d|dt_bias)$", (None,)),  # (heads,)
    (r"(norm|scale|bias)", None),  # norms etc: replicate
    (r"(^|/)(ln|post_ln)\d*$", None),  # layer-norm scales: replicate
    (r"(cross_wq|cross_wk|cross_wv)$", (FSDP, TP)),
    (r"cross_wo$", (TP, FSDP)),
]


def param_spec(path: str, shape: tuple, sizes: dict, *, fsdp: bool = True) -> P:
    """``fsdp=False`` drops the ZeRO-3 data-axis sharding (params/opt are
    then replicated over data, TP-sharded over model) — the right choice
    when the optimizer state fits, since it removes the per-microbatch
    weight all-gathers (EXPERIMENTS.md §Perf iteration 5)."""
    def strip(entry):
        if not fsdp:
            if entry == FSDP:
                return None
            if isinstance(entry, tuple):
                entry = tuple(a for a in entry if a != FSDP) or None
        return entry

    for pat, spec in _RULES:
        if re.search(pat, path):
            if spec is None:
                return P()
            spec = tuple(spec[-len(shape):]) if len(spec) <= len(shape) else spec
            full = (None,) * (len(shape) - len(spec)) + tuple(spec)
            full = tuple(strip(e) for e in full)
            return resolve_spec(full, shape, sizes)
    if len(shape) < 2 or not fsdp:  # unmatched vectors/scalars: replicate
        return P()
    # default: FSDP on the largest divisible dim
    best, best_dim = None, 0
    for i, d in enumerate(shape):
        if d > best_dim and sizes.get(FSDP, 1) > 0 and d % max(sizes.get(FSDP, 1), 1) == 0:
            best, best_dim = i, d
    spec = [None] * len(shape)
    if best is not None and sizes.get(FSDP):
        spec[best] = FSDP
    return P(*spec)


def param_specs(params, mesh, *, fsdp: bool = True) -> object:
    """Pytree of PartitionSpec mirroring ``params`` (works on shape structs)."""
    sizes = mesh_axis_sizes(mesh)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def path_str(kp):
        return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)

    specs = {path_str(kp): param_spec(path_str(kp), v.shape, sizes, fsdp=fsdp) for kp, v in flat}
    leaves, treedef = jax.tree_util.tree_flatten(params)
    out = [specs[path_str(kp)] for kp, _ in flat]
    return jax.tree_util.tree_unflatten(treedef, out)
