"""Device-mesh construction — the TPU-native "communicator".

The reference's communicator is implicit: Horovod ranks 0..N-1 joined in one
NCCL/MPI world (SURVEY.md §2 L0–L1).  On TPU the analogous object is a
``jax.sharding.Mesh``: a named, possibly multi-dimensional view of the chips.
The reference is pure data-parallel (SURVEY.md §3c), so the default mesh is
1-D over a ``data`` axis; we still carry optional ``model`` / ``seq`` /
``pipe`` / ``expert`` axes (size 1 by default) so shardings composed against
this mesh do not need rewriting when a workload later turns those on — the
design requirement in SURVEY.md §5.7 that the mesh not preclude extra axes.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# Canonical axis order. Data-parallel outermost so its collectives ride the
# slowest-varying physical dimension (and DCN when a mesh spans slices);
# model/seq innermost so their heavier collectives stay on nearest-neighbor ICI.
AXES = ("data", "fsdp", "pipe", "seq", "expert", "model")

# Multi-slice meshes carry one extra DCN axis *outside* every ICI axis: the
# slice axis must be the slowest-varying dimension so that only collectives
# which genuinely span slices ride the (much slower) data-center network.
SLICE_AXIS = "slice"

# The axes over which a global batch is partitioned. Batch-like arrays shard
# over all of these; fsdp contributes to the data-parallel world size.
BATCH_AXES = ("data", "fsdp")


@dataclass(frozen=True)
class MeshSpec:
    """Logical parallelism degrees. -1 on ``data`` means "all remaining chips".

    ``slices > 1`` declares a hierarchical ICI×DCN topology: the ICI axes
    describe one slice, and a ``slice`` axis of that size is prepended
    outermost.  ``slices == 1`` (the default) produces the exact same mesh
    as before the axis existed — single-slice programs see zero drift.
    """

    data: int = -1
    fsdp: int = 1
    pipe: int = 1
    seq: int = 1
    expert: int = 1
    model: int = 1
    slices: int = 1

    def axis_names(self) -> tuple[str, ...]:
        return (SLICE_AXIS, *AXES) if self.slices > 1 else AXES

    def sizes(self, n_devices: int) -> dict[str, int]:
        if self.slices < 1:
            raise ValueError(f"slices must be >= 1, got {self.slices}")
        sizes = {
            "data": self.data,
            "fsdp": self.fsdp,
            "pipe": self.pipe,
            "seq": self.seq,
            "expert": self.expert,
            "model": self.model,
        }
        if self.slices > 1:
            sizes = {SLICE_AXIS: self.slices, **sizes}
        fixed = int(np.prod([v for v in sizes.values() if v != -1]))
        n_wild = sum(1 for v in sizes.values() if v == -1)
        if n_wild > 1:
            raise ValueError("at most one mesh axis may be -1")
        if n_wild == 1:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            wild = n_devices // fixed
            sizes = {k: (wild if v == -1 else v) for k, v in sizes.items()}
        total = int(np.prod(list(sizes.values())))
        if total != n_devices:
            raise ValueError(
                f"mesh {sizes} covers {total} devices but {n_devices} are present"
            )
        return sizes


def make_mesh(
    spec: MeshSpec | None = None,
    *,
    devices: list[jax.Device] | None = None,
) -> Mesh:
    """Build the framework's device mesh.

    Defaults to a pure data-parallel mesh over every visible chip — the
    reference's (only) topology, SURVEY.md §3c.  ``jax.make_mesh`` internally
    reorders devices to match the physical ICI torus when running on real TPU
    slices, so collectives over the trailing axes map to neighbor links.

    Every axis of every mesh is ``AxisType.Auto``.  The step programs here
    are either ``shard_map`` bodies (axes Manual inside) or ``jit`` programs
    whose collectives GSPMD propagation inserts from the in/out shardings;
    none is written for sharding-in-types.  ``jax.make_mesh``'s own default
    is Explicit, under which the same programs raise ``ShardingTypeError``
    on the first ambiguous op, so the type is stated, not inherited.
    """
    spec = spec or MeshSpec()
    all_devices = jax.devices()
    devices = devices if devices is not None else all_devices
    sizes = spec.sizes(len(devices))
    axes = spec.axis_names()
    shape = tuple(sizes[a] for a in axes)
    auto = (AxisType.Auto,) * len(axes)
    if [d.id for d in devices] == [d.id for d in all_devices]:
        # Full-device meshes go through jax.make_mesh, which reorders devices
        # to match the physical ICI torus on real TPU slices.
        return jax.make_mesh(shape, axes, devices=devices, axis_types=auto)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, axes, axis_types=auto)


def best_effort_mesh(max_devices: int | None = None) -> Mesh:
    """Data-parallel mesh over up to ``max_devices`` chips (for tests/bench)."""
    devices = jax.devices()
    if max_devices is not None:
        devices = devices[:max_devices]
    return make_mesh(MeshSpec(data=len(devices)), devices=devices)


def batch_axes(mesh: Mesh | None = None) -> tuple[str, ...]:
    """The axes a global batch shards over, for this mesh's topology.

    On a hierarchical mesh the slice axis is batch-like too — each slice
    works on its own shard of the batch and only gradients cross DCN — so
    it joins ``data``/``fsdp`` (outermost, matching mesh axis order).
    """
    if mesh is not None and SLICE_AXIS in mesh.shape:
        return (SLICE_AXIS, *BATCH_AXES)
    return BATCH_AXES


def data_parallel_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in batch_axes(mesh)]))


def batch_spec(extra: tuple = (), *, mesh: Mesh | None = None) -> P:
    """PartitionSpec for batch-major arrays: leading dim over the batch axes."""
    return P(batch_axes(mesh), *extra)


def replicated_spec() -> P:
    return P()


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_spec(mesh=mesh))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, replicated_spec())


def host_device_put(x, sharding: NamedSharding):
    """Multi-host-safe placement of host data.

    ``jax.device_put`` rejects shardings spanning non-addressable devices;
    on multi-host meshes each process contributes its shard via
    ``make_array_from_callback``.  Handles PRNG-key (extended-dtype) leaves,
    which numpy cannot represent directly."""
    if jax.process_count() == 1 or sharding.is_fully_addressable:
        return jax.device_put(x, sharding)
    if hasattr(x, "dtype") and jax.dtypes.issubdtype(x.dtype, jax.dtypes.extended):
        data = host_device_put(jax.random.key_data(x), sharding)
        return jax.random.wrap_key_data(data, impl=jax.random.key_impl(x))
    arr = np.asarray(x)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx])


def local_batch_size(mesh: Mesh, global_batch: int) -> int:
    """Per-host batch share (reference: DistributedSampler num_replicas/rank
    partitioning, SURVEY.md §3a 'GCS data loader')."""
    dp = data_parallel_size(mesh)
    if global_batch % dp != 0:
        raise ValueError(f"global batch {global_batch} not divisible by dp={dp}")
    # Each host feeds its local devices; global batch / process_count rows.
    n_proc = max(1, jax.process_count())
    if global_batch % n_proc != 0:
        raise ValueError(f"global batch {global_batch} not divisible by hosts={n_proc}")
    return global_batch // n_proc
