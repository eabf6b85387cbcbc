"""FSDP / ZeRO-style parameter + optimizer-state sharding over ``fsdp``.

The reference is pure replicated-parameter data parallelism (SURVEY.md §3c);
its optimizer state is replicated on every GPU.  On TPU the idiomatic
memory-scaling upgrade is sharding parameters and optimizer state across a
mesh axis and letting XLA's SPMD partitioner insert the all-gathers (before
use) and reduce-scatters (of gradients) — cross-replica weight-update
sharding (PAPERS.md:5) generalized to ZeRO-3.  No runtime machinery: the
sharding is a *placement decision* expressed as ``NamedSharding``s on the
``TrainState`` pytree, consumed by the auto-SPMD (``mode="jit"``) train step.

Rule: each array leaf shards its largest dimension divisible by the fsdp
axis size; indivisible or tiny leaves stay replicated.  The same rule
applied to the optimizer state (whose momentum/variance leaves mirror the
param shapes) yields consistent placement for the whole update.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

MIN_SHARD_ELEMENTS = 1024  # below this, sharding overhead beats the savings


def choose_spec(shape: tuple[int, ...], fsdp_size: int,
                axis: str = "fsdp") -> P:
    """Shard the largest divisible dim of ``shape`` over ``axis``."""
    if fsdp_size <= 1 or int(np.prod(shape or (1,))) < MIN_SHARD_ELEMENTS:
        return P()
    dims = sorted(range(len(shape)), key=lambda i: shape[i], reverse=True)
    for i in dims:
        if shape[i] % fsdp_size == 0:
            spec = [None] * len(shape)
            spec[i] = axis
            return P(*spec)
    return P()


def state_shardings(state: PyTree, mesh: Mesh, axis: str = "fsdp",
                    *, tp_rules=None, tp_axis: str = "model") -> PyTree:
    """NamedSharding tree for a TrainState (or any pytree of arrays).

    With ``tp_rules`` (tpuframe.parallel.tp) the tensor-parallel spec is
    applied first by parameter path; the ``fsdp`` axis then shards the
    largest *still-unsharded* divisible dim of each leaf — composing
    ZeRO × TP from placement alone.
    """
    size = mesh.shape[axis]
    axis_sizes = dict(mesh.shape) if tp_rules else None
    flat, treedef = jax.tree_util.tree_flatten_with_path(state)

    def path_str(path) -> str:
        parts = []
        for k in path:
            for attr in ("key", "name", "idx"):
                if hasattr(k, attr):
                    parts.append(str(getattr(k, attr)))
                    break
            else:
                parts.append(str(k))
        return "/".join(parts)

    out = []
    for path, x in flat:
        shape = tuple(getattr(x, "shape", ()))
        base = None
        if axis_sizes is not None:
            from tpuframe.parallel import tp as tp_lib

            base = tp_lib.match_spec(path_str(path), shape, axis_sizes,
                                     tp_rules)
        spec = _add_fsdp(shape, base, size, axis)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def _add_fsdp(shape: tuple[int, ...], base: P | None, fsdp_size: int,
              axis: str) -> P:
    """Overlay the fsdp axis on the largest unsharded divisible dim."""
    entries = list(base) + [None] * (len(shape) - len(base)) if base else         [None] * len(shape)
    if fsdp_size <= 1 or int(np.prod(shape or (1,))) < MIN_SHARD_ELEMENTS:
        return P(*entries) if base else P()
    dims = sorted(range(len(shape)), key=lambda i: shape[i], reverse=True)
    for i in dims:
        if entries[i] is None and shape[i] % fsdp_size == 0:
            entries[i] = axis
            return P(*entries)
    return P(*entries) if base else P()


def shard_state(state: PyTree, mesh: Mesh, axis: str = "fsdp") -> PyTree:
    """Place a (host or replicated) TrainState with fsdp shardings."""
    from tpuframe.parallel import mesh as mesh_lib

    shardings = state_shardings(state, mesh, axis)
    return jax.tree.map(mesh_lib.host_device_put, state, shardings)


def param_fraction_sharded(state: PyTree, axis: str = "fsdp") -> float:
    """Diagnostics: fraction of state elements whose placement splits ``axis``
    (used by tests and the harness banner)."""
    total, sharded = 0, 0
    for leaf in jax.tree.leaves(state):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        total += n
        sharding = getattr(leaf, "sharding", None)
        spec = getattr(sharding, "spec", None)
        if spec is not None and any(
                (ax == axis or (isinstance(ax, tuple) and axis in ax))
                for ax in spec if ax is not None):
            sharded += n
    return sharded / max(total, 1)
