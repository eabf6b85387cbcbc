"""Collective primitives — XLA replacements for Horovod's op set.

Reference capability (SURVEY.md §3b): Horovod exposes allreduce / allgather /
broadcast / alltoall, executed by a C++ background runtime over NCCL rings
with tensor fusion.  Under XLA SPMD none of that is runtime code: these
helpers trace to ``lax`` collective HLOs inside a compiled program, XLA's
combiner pass does the fusion (see ``tpuframe.parallel.tuning``), and the TPU
ICI torus provides bandwidth-optimal routing in hardware.

Two usage modes, mirroring how the reference uses Horovod:
  - inside a ``shard_map``-ed step function (per-grad allreduce, metric
    averaging) — call these directly with an axis name;
  - at the harness level on host values (eval metric averaging, parameter
    broadcast at init) — use ``cross_replica_mean`` / ``host_broadcast`` which
    jit a tiny collective program over a mesh.

Axis names may be a single name or a tuple (e.g. ``("data", "fsdp")``).
"""

from __future__ import annotations

from typing import Any, Sequence

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
# The one reach into jax._src: jax 0.9.0 has the Varying -> Invariant gather
# but does not export it under jax.lax.  A jax that moves it fails this import
# (tests/test_parallel_core.py::TestAllgatherInvariant says why).
from jax._src.lax.parallel import all_gather_invariant as _all_gather_invariant
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpuframe.parallel import mesh as mesh_lib

AxisName = str | Sequence[str]
PyTree = Any


def _bound_axes(axis: AxisName) -> tuple[str, ...]:
    """The subset of ``axis`` names bound by an enclosing shard_map/pmap trace.

    Collectives here reduce over whichever requested axes exist, so the same
    step function runs under a full mesh, a pmap with only ``data`` bound, or
    completely unmapped (single-process config 1) — the laptop-to-pod property
    the reference gets from Horovod's size()==1 no-op mode.
    """
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    bound = []
    for n in names:
        try:
            lax.axis_size(n)
        except NameError:
            continue
        bound.append(n)
    return tuple(bound)


def _in_mapped_context(axis: AxisName) -> bool:
    """True when every name in ``axis`` is bound by an enclosing trace."""
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    return len(_bound_axes(names)) == len(names)


def allreduce(x: PyTree, axis: AxisName = "data", *, average: bool = True) -> PyTree:
    """Sum (or mean) a pytree across the mapped axis.

    Reference parity: ``hvd.allreduce(tensor, average=True)`` (SURVEY.md §3a
    "Distributed glue").  Degrades to identity when the axis is not bound —
    so the same step function runs unmapped in config 1's single-process mode
    (SURVEY.md §7 build order step 1).
    """
    return _elementwise_reduce(x, axis, lax.pmean if average else lax.psum)


def average_gradients(grads: PyTree, axis: AxisName = "data") -> PyTree:
    """Make ``grads`` the cross-replica *average* regardless of how they were
    produced.

    Two arrival states inside a shard_map trace (jax's vma semantics):
      - varying leaves (grad of a per-shard loss w.r.t. ``pvary``-ed params,
        or hand-built values): need an explicit ``pmean``;
      - unvarying leaves (grad w.r.t. replicated params — autodiff's transpose
        of the implicit pbroadcast already inserted the ``psum``): the sum is
        done; divide by the world size.

    This is the exact semantic of Horovod's averaged grad allreduce, which is
    why ``hvd.DistributedOptimizer`` routes through here (SURVEY.md §4.1).
    """
    names = _bound_axes(axis)
    if not names:
        return grads

    def _avg(g):
        vma = jax.typeof(g).vma
        varying = [a for a in names if a in vma]
        presummed = [a for a in names if a not in vma]
        out = lax.pmean(g, varying) if varying else g
        size_presummed = 1
        for name in presummed:
            size_presummed *= lax.axis_size(name)
        return out / size_presummed if size_presummed > 1 else out

    return _maybe_fused_reduce(grads, names, _avg, mean=True)


def sum_gradients(grads: PyTree, axis: AxisName = "data") -> PyTree:
    """Cross-replica *sum* with the same vma-awareness as
    ``average_gradients``: pre-summed (unvarying) leaves pass through instead
    of being double-counted by another psum."""
    names = _bound_axes(axis)
    if not names:
        return grads

    def _sum(g):
        vma = jax.typeof(g).vma
        varying = [a for a in names if a in vma]
        return lax.psum(g, varying) if varying else g

    return _maybe_fused_reduce(grads, names, _sum, mean=False)


def _maybe_fused_reduce(grads: PyTree, names, per_leaf, *, mean: bool) -> PyTree:
    """Knob routing shared by average_/sum_gradients: with
    TPUFRAME_FUSION_THRESHOLD set, fully-varying leaves reduce through the
    packed fusion buffers (tpuframe.parallel.fusion) so the hvd facade's
    DistributedOptimizer has the same knob semantics as the step builder;
    mixed/presummed leaves (and the knob-unset default) keep the per-leaf
    vma-aware path."""
    from tpuframe.parallel import tuning

    threshold = tuning.step_threshold()
    if not threshold or threshold <= 0:
        return jax.tree.map(per_leaf, grads)
    from tpuframe.parallel import fusion

    leaves, treedef = jax.tree.flatten(grads)
    fused_idx = [i for i, g in enumerate(leaves)
                 if all(a in jax.typeof(g).vma for a in names)]
    out = {i: per_leaf(leaves[i])
           for i in set(range(len(leaves))) - set(fused_idx)}
    if fused_idx:
        reduced = fusion.fused_psum([leaves[i] for i in fused_idx], names,
                                    threshold_bytes=threshold, mean=mean)
        out.update(dict(zip(fused_idx, reduced)))
    return jax.tree.unflatten(treedef, [out[i] for i in range(len(leaves))])


def allgather(x: jax.Array, axis: AxisName = "data", *, tiled: bool = True) -> jax.Array:
    """Concatenate each shard's value along dim 0 (Horovod allgather).
    Unmapped (world of 1): identity, matching the other collectives'
    single-process no-op contract."""
    bound = _bound_axes(axis)
    if not bound:
        return x
    return lax.all_gather(x, bound, axis=0, tiled=tiled)


def allgather_invariant(x: jax.Array, axis: AxisName = "data", *,
                        gather_axis: int = 0, tiled: bool = True) -> jax.Array:
    """All-gather whose result is replication-INVARIANT: every replica
    gathers the identical full array, so the output is legal under a
    replicated out_spec (the zero1 param regather and the two-level
    gather rely on this; ``lax.all_gather``'s result is varying and is
    refused there).  Unmapped: identity."""
    bound = _bound_axes(axis)
    if not bound:
        return x
    return _all_gather_invariant(x, bound, axis=gather_axis, tiled=tiled)


def _linear_index(bound: tuple[str, ...]) -> jax.Array:
    """Row-major linearized replica index over the bound axes — the single
    rank space Horovod exposes (``hvd.rank()`` in its one-process-per-GPU
    model), reconstructed from the mesh position.

    Size-1 axes are skipped: their index is identically 0, and touching
    ``axis_index`` on them would mark the result varying over axes it
    cannot actually vary over (breaking callers' out_specs inference).
    """
    sized = _sized_axes(bound)
    if not sized:
        return jnp.zeros((), jnp.int32)
    if len(sized) == 1:
        return lax.axis_index(sized[0])
    idx = jnp.zeros((), jnp.int32)
    for name in sized:
        idx = idx * lax.axis_size(name) + lax.axis_index(name)
    return idx


def _sized_axes(bound: tuple[str, ...]) -> tuple[str, ...]:
    """Bound axes with size > 1 — the axes a reduction can actually act on.
    Size-1 axes are no-ops whose inclusion only confuses vma inference."""
    return tuple(n for n in bound if lax.axis_size(n) > 1)


def _vary_over(t, axes: tuple[str, ...]):
    """Make ``t`` vma-varying over every axis in ``axes`` so a collective can
    legally reduce over all of them at once (a replicated leaf counts once
    per mesh position — Horovod's rank-space semantics, where duplicate
    values on distinct ranks are still distinct contributions)."""
    missing = tuple(a for a in axes if a not in jax.typeof(t).vma)
    return lax.pcast(t, missing, to="varying") if missing else t


def _clear_unit_axes(t, bound: tuple[str, ...]):
    """Mark ``t`` reduced over any size-1 bound axes it is vma-varying on.

    Reductions here act only on the >1-sized axes, but a reduction over the
    whole ``bound`` tuple must still come back replicated over ALL of it —
    callers' ``out_specs`` rely on that (the single-device "config 1" mode
    maps a size-1 data axis).  psum over a size-1 axis is a value identity
    the compiler elides; it exists purely to update the vma state.
    """
    small = tuple(a for a in bound
                  if lax.axis_size(a) == 1 and a in jax.typeof(t).vma)
    return lax.psum(t, small) if small else t


def broadcast(x: PyTree, axis: AxisName = "data", *, root: int = 0) -> PyTree:
    """Every member takes root's value (Horovod broadcast).

    Implemented as select+psum rather than a dedicated HLO: XLA pattern-matches
    this to a broadcast-like collective, and it stays differentiable.
    """
    bound = _bound_axes(axis)
    if not bound:
        return x
    sized = _sized_axes(bound)
    if not sized:
        return jax.tree.map(lambda t: _clear_unit_axes(t, bound), x)
    _check_ranks(bound, (root,))  # an unmatched root would psum to zeros
    idx = _linear_index(bound)

    def _bcast(t):
        masked = jnp.where(idx == root, _vary_over(t, sized),
                           jnp.zeros_like(t))
        return _clear_unit_axes(lax.psum(masked, sized), bound)

    return jax.tree.map(_bcast, x)


def alltoall(x: jax.Array, axis: AxisName = "data", *, split_axis: int = 0,
             concat_axis: int = 0) -> jax.Array:
    """Horovod alltoall: scatter dim ``split_axis``, gather along ``concat_axis``.

    On TPU this lowers to the ICI AllToAll used by sequence/expert parallelism
    (kept first-class so a seq/expert axis can ride it later, SURVEY.md §5.7).
    Unmapped: identity (a 1-member alltoall is a copy).
    """
    bound = _bound_axes(axis)
    if not bound:
        return x
    return lax.all_to_all(x, bound, split_axis=split_axis, concat_axis=concat_axis,
                          tiled=True)


def ring_permute(x: jax.Array, axis: AxisName = "data", *, shift: int = 1) -> jax.Array:
    """Send each shard to its ring neighbor (basis of ring-attention-style
    pipelining; maps to CollectivePermute on neighbor ICI links).
    Unmapped: identity (a 1-ring permute is a self-send)."""
    bound = _bound_axes(axis)
    if not bound:
        return x
    if len(bound) != 1:
        raise ValueError(f"ring_permute needs exactly one axis, got {bound}")
    n = lax.axis_size(bound[0])
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, bound[0], perm=perm)


def reduce_scatter(x: jax.Array, axis: AxisName = "data", *, scatter_axis: int = 0,
                   average: bool = False) -> jax.Array:
    """psum_scatter — the building block of sharded-optimizer updates
    (cross-replica weight-update sharding, PAPERS.md:5; the zero1 path's
    gradient reduction).  Unmapped: identity (reduce over a world of 1).

    ``x.shape[scatter_axis]`` must divide evenly by the member count —
    psum_scatter has no remainder path, and the shape error it raises
    from deep inside lowering is unreadable; callers that need uneven
    leaves pad first (``zero1``'s pad-to-multiple layout)."""
    bound = _bound_axes(axis)
    if not bound:
        return x
    n = 1
    for name in bound:
        n *= lax.axis_size(name)
    dim = x.shape[scatter_axis] if x.ndim else 0
    if dim % n:
        raise ValueError(
            f"reduce_scatter: dim {scatter_axis} of shape {tuple(x.shape)} "
            f"({dim}) is not divisible by the {n}-member axis {bound}; "
            f"pad the leading dim to a multiple of {n} first (see "
            f"tpuframe.parallel.zero1's pad-to-multiple layout)")
    out = lax.psum_scatter(x, bound, scatter_dimension=scatter_axis, tiled=True)
    if average:
        out = out / n
    return out


# ---------------------------------------------------------------------------
# Host-level (outside shard_map) collectives over a mesh
# ---------------------------------------------------------------------------

def cross_replica_mean(tree: PyTree, mesh: Mesh | None = None) -> PyTree:
    """Average genuinely per-process host values across all processes.

    Reference parity: the eval-loop ``hvd.allreduce(metric_tensor)`` one-shot
    collective (SURVEY.md §4.5).  Every process calls this with its OWN local
    value (e.g. a per-host eval accuracy); the result is the cross-process
    mean, identical on every process.  Single-process: identity (Horovod's
    size()==1 no-op contract).  ``mesh`` is accepted for signature
    compatibility but unused — the reduction runs over a one-device-per-
    process mesh built here, so it works regardless of the caller's mesh.
    """
    del mesh
    nproc = jax.process_count()
    if nproc == 1:
        return jax.tree.map(lambda t: jnp.asarray(t, jnp.float32), tree)

    import numpy as np

    # One device per process, in process order — each process contributes one
    # row of the stacked array via make_array_from_process_local_data.
    per_proc: dict[int, Any] = {}
    for d in jax.devices():
        per_proc.setdefault(d.process_index, d)
    devs = [per_proc[i] for i in sorted(per_proc)]
    # One-device-per-process host mesh for cross-process gathers — a
    # degenerate transport detail, not a training-axis mesh.
    pmesh = Mesh(np.asarray(devs), ("proc",))  # tf-lint: ok[TF119]
    sharding = NamedSharding(pmesh, P("proc"))

    def _mean(leaf):
        local = np.asarray(leaf, np.float32)[None]
        garr = jax.make_array_from_process_local_data(
            sharding, local, (nproc, *local.shape[1:]))
        return jnp.mean(garr, axis=0)

    return jax.tree.map(_mean, tree)


def primary_device_put(x, sharding: NamedSharding) -> jax.Array:
    """Replicate process-0's host value onto every device, shipping the bytes
    over the device interconnect (ICI/DCN) instead of having each host supply
    its own copy.

    The checkpoint-restore counterpart of the reference's rank-0
    ``torch.load`` + ``hvd.broadcast_parameters`` (SURVEY.md §4.4): the
    primary host reads from storage once and the fabric fans the data out —
    storage traffic is O(bytes), not O(hosts × bytes).  Non-primary
    processes pass a same-shape/dtype placeholder (contents ignored).

    ``sharding`` must be fully replicated over a mesh spanning all devices.
    Mechanism: one row per device, process-0's first-device row carries the
    payload and every other row is zero, then an on-device sum over the row
    axis replicates the payload everywhere (one all-reduce-shaped transfer).
    """
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    if not sharding.is_fully_replicated:
        raise ValueError("primary_device_put needs a fully-replicated "
                         f"sharding, got {sharding}")
    if hasattr(x, "dtype") and jax.dtypes.issubdtype(x.dtype, jax.dtypes.extended):
        data = primary_device_put(jax.random.key_data(x), sharding)
        return jax.random.wrap_key_data(data, impl=jax.random.key_impl(x))

    arr = np.asarray(x)
    as_bool = arr.dtype == np.bool_
    if as_bool:
        arr = arr.view(np.uint8)
    # Row mesh built from the TARGET sharding's own device order — on real
    # TPU slices jax.make_mesh reorders devices to the ICI torus, so
    # jax.devices() order and the caller's mesh order differ; deriving both
    # sides from one order keeps the jit's input and output compatible.
    devs = list(sharding.mesh.devices.flat)
    # Broadcast-row host mesh in the caller's device order — transport
    # detail, same class as the proc mesh above.
    pmesh = Mesh(np.asarray(devs), ("bcast",))  # tf-lint: ok[TF119]
    rows = NamedSharding(pmesh, P("bcast"))
    payload_row = min(i for i, d in enumerate(devs) if d.process_index == 0)
    # One shared zero row (not a local_devices×leaf buffer): host RAM stays
    # O(leaf), and only the payload row carries real data.
    zero_row = np.zeros((1, *arr.shape), arr.dtype)
    pieces = [
        jax.device_put(arr[None] if i == payload_row else zero_row, d)
        for i, d in enumerate(devs)
        if d.process_index == jax.process_index()
    ]
    garr = jax.make_array_from_single_device_arrays(
        (len(devs), *arr.shape), rows, pieces)
    out = _bcast_sum(sharding)(garr)
    return out.astype(jnp.bool_) if as_bool else out


@functools.lru_cache(maxsize=64)
def _bcast_sum(sharding: NamedSharding):
    """One jitted sum-over-rows program per target sharding — restore calls
    primary_device_put once per leaf; a fresh jit per call would recompile
    the same trivial program hundreds of times per restart."""
    return jax.jit(lambda a: a.sum(axis=0), out_shardings=sharding)


def host_broadcast(tree: PyTree, mesh: Mesh) -> PyTree:
    """Replicate host-0-computed values onto every device of the mesh
    (reference parity: ``hvd.broadcast_parameters`` from rank 0 at start,
    SURVEY.md §4.1).  Under SPMD every process must call this with the same
    structure; data content is taken from the fully-replicated device copy."""
    sharding = mesh_lib.replicated_sharding(mesh)
    return jax.tree.map(lambda t: jax.device_put(t, sharding), tree)


def device_count(axis_env_size: int | None = None) -> int:
    return axis_env_size or jax.device_count()


def psum_scalar(value: float | jax.Array, axis: AxisName = "data") -> jax.Array:
    """Scalar psum usable in metric dicts inside step functions."""
    if not _in_mapped_context(axis):
        return jnp.asarray(value)
    return lax.psum(jnp.asarray(value), axis)


def reduce_min(x: PyTree, axis: AxisName = "data") -> PyTree:
    """Elementwise cross-replica minimum (Horovod ``op=hvd.Min``)."""
    return _elementwise_reduce(x, axis, lax.pmin)


def reduce_max(x: PyTree, axis: AxisName = "data") -> PyTree:
    """Elementwise cross-replica maximum (Horovod ``op=hvd.Max``)."""
    return _elementwise_reduce(x, axis, lax.pmax)


def _elementwise_reduce(x: PyTree, axis: AxisName, op) -> PyTree:
    """Shared guard chain for psum/pmean/pmin/pmax-style reductions.

    ``_vary_over``: a leaf replicated along one sized axis but varying along
    another would otherwise present a mixed vma state the collective
    rejects; counting it once per mesh position is Horovod's rank-space
    semantics.  ``_clear_unit_axes``: outputs come back replicated over the
    size-1 bound axes too, preserving callers' out_specs expectations.
    """
    bound = _bound_axes(axis)
    if not bound:
        return x
    sized = _sized_axes(bound)
    if not sized:
        return jax.tree.map(lambda t: _clear_unit_axes(t, bound), x)
    return jax.tree.map(
        lambda t: _clear_unit_axes(op(_vary_over(t, sized), sized), bound), x)


def reduce_prod(x: PyTree, axis: AxisName = "data") -> PyTree:
    """Elementwise cross-replica product (Horovod ``op=hvd.Product``).

    XLA has no product all-reduce HLO; the sound formulation (zeros and
    negative values included — a log/exp trick would not be) is all_gather
    then a local product over the gathered axis.  Product reductions are a
    metrics-sized verb in practice, so the gather's N× wire traffic does
    not matter.
    """
    bound = _bound_axes(axis)
    if not bound:
        return x
    sized = _sized_axes(bound)
    if not sized:
        return jax.tree.map(lambda t: _clear_unit_axes(t, bound), x)

    def _prod(t):
        gathered = lax.all_gather(_vary_over(t, sized), sized, axis=0,
                                  tiled=False)
        # Every replica computes the identical product from the gathered
        # copies, but vma can't see through all_gather: pmax of identical
        # values is a bit-exact identity that marks the result reduced.
        return _clear_unit_axes(lax.pmax(jnp.prod(gathered, axis=0), sized),
                                bound)

    return jax.tree.map(_prod, x)


def adasum(tree: PyTree, axis: AxisName = "data") -> PyTree:
    """Adaptive summation (Horovod ``op=hvd.Adasum``, arXiv:2006.02924).

    The pairwise combine is scale-insensitive: for gradients ``a, b``

        adasum(a, b) = (1 - a.b / 2|a|^2) a  +  (1 - a.b / 2|b|^2) b

    which is the *mean* when a == b (each coefficient becomes 1/2) and the
    *sum* when a ⟂ b — interpolating between LR-scaling regimes, which is
    the whole point of the op.  Horovod runs it as a recursive-halving
    tree in its C++ runtime; the SPMD-native realization is a ppermute
    BUTTERFLY: at stage k every replica exchanges with ``index XOR 2^k``
    and applies the (symmetric) combine, so all replicas hold the identical
    reduction after log2(N) stages — same pairing tree, no runtime thread.

    Norm/dot accumulation is f32 regardless of input dtype.  Requires a
    power-of-two replica count (TPU mesh axes are powers of two); the
    butterfly pairing has no remainder path.

    Arrival-state caveat (cf. ``average_gradients``): Adasum needs the RAW
    per-replica gradients.  Under shard_map autodiff, grads of replicated
    (unvarying) params arrive ALREADY psum'd — identical on every replica —
    and adasum of identical vectors is the identity, so a pre-summed leaf
    passes through as the cross-replica SUM, not the adaptive combine.  To
    get true Adasum semantics compute per-shard losses against ``pvary``-ed
    params so grads stay varying (the harness's step builder does).
    """
    names = _bound_axes(axis)
    if not names:
        return tree
    # Multiple bound axes: sequential per-axis butterflies (equivalent to
    # one big butterfly up to Adasum's own pairing-tree dependence — the op
    # is not associative, and Horovod's own result likewise depends on its
    # reduction-tree shape).
    if len(names) > 1:
        out = tree
        for a in names:
            out = adasum(out, a)
        return out
    (name,) = names
    n = lax.axis_size(name)
    if n & (n - 1):
        raise ValueError(f"adasum butterfly needs a power-of-two replica "
                         f"count, got {n} over {name!r}")
    if n == 1:
        return jax.tree.map(lambda t: _clear_unit_axes(t, names), tree)

    def _ada(x):
        # Pre-summed (unvarying) leaves enter the butterfly as identical
        # vectors and come out unchanged — the documented degrade-to-sum;
        # without the cast, ppermute rejects the unvarying operand outright.
        # Trace-time warning (PORTING.md Adasum caveat 2): statically
        # detectable, and silent sum-semantics is exactly the surprise a
        # porting user hits — the harness's local-grads path never does.
        if name not in jax.typeof(x).vma:
            import warnings

            warnings.warn(
                f"adasum over {name!r}: leaf is unvarying (already reduced "
                f"over the axis) — the butterfly is an identity on it, so "
                f"you get SUM semantics, not the adaptive combine. Feed "
                f"adasum the raw per-replica gradients (see PORTING.md).",
                stacklevel=3)
        v = _vary_over(x.astype(jnp.float32), (name,))
        for k in range(n.bit_length() - 1):
            dist = 1 << k
            perm = [(i, i ^ dist) for i in range(n)]
            other = lax.ppermute(v, name, perm)
            dot = jnp.vdot(v, other)
            na = jnp.vdot(v, v)
            nb = jnp.vdot(other, other)
            ca = jnp.where(na > 0, dot / (2.0 * na), 0.0)
            cb = jnp.where(nb > 0, dot / (2.0 * nb), 0.0)
            v = (1.0 - ca) * v + (1.0 - cb) * other
        # All replicas now hold the identical combined value, but the vma
        # system cannot infer that through ppermute.  pmax of identical
        # values is a BIT-EXACT identity (unlike pmean, whose re-summation
        # can round) and marks the leaf reduced over the axis — at the cost
        # of one extra gradient-sized collective, which is in the spirit of
        # the op (Horovod's Adasum tree is likewise pricier than a ring).
        return lax.pmax(v, name).astype(x.dtype)

    return jax.tree.map(_ada, tree)


def _member_mask(bound: tuple[str, ...], ranks: Sequence[int]) -> jax.Array:
    """Boolean scalar: is this replica's linearized rank in ``ranks``?"""
    idx = _linear_index(bound)
    member = jnp.zeros((), bool)
    for r in ranks:
        member = member | (idx == r)
    return member


def _check_ranks(bound: tuple[str, ...], ranks: Sequence[int]) -> None:
    """Trace-time validation: every rank must exist in the linearized rank
    space, else masked/rooted collectives silently drop contributions (an
    out-of-range or negative rank never matches any replica's index) —
    Horovod raises for invalid ranks too."""
    world = 1
    for a in _sized_axes(bound):
        world *= lax.axis_size(a)
    bad = [int(r) for r in ranks if int(r) >= world or int(r) < 0]
    if bad:
        raise ValueError(f"ranks {bad} out of range for a "
                         f"{world}-replica axis {bound}")


def masked_allreduce(x: PyTree, axis: AxisName, ranks: Sequence[int], *,
                     average: bool = True) -> PyTree:
    """Allreduce restricted to the replicas in ``ranks`` (Horovod
    ``process_set=``): members receive the subgroup sum/mean, NON-members
    keep their input unchanged — Horovod's op simply never runs on ranks
    outside the set.

    Realized as a masked reduction over the full axis (zero contributions
    from non-members, static divisor ``len(ranks)``) — one full-axis psum
    instead of a subgroup communicator, which XLA then routes over the same
    ICI links a subgroup ring would use.
    """
    bound = _bound_axes(axis)
    if not bound:
        return x
    sized = _sized_axes(bound)
    if not sized:
        return jax.tree.map(lambda t: _clear_unit_axes(t, bound), x)
    _check_ranks(bound, ranks)
    m = _member_mask(bound, ranks)
    count = len(set(int(r) for r in ranks))

    def _f(t):
        contrib = jnp.where(m, _vary_over(t, sized), jnp.zeros_like(t))
        total = lax.psum(contrib, sized)
        if average:
            total = (total.astype(jnp.float32) / count).astype(t.dtype)
        return _clear_unit_axes(jnp.where(m, total, t), bound)

    return jax.tree.map(_f, x)


def masked_broadcast(x: PyTree, axis: AxisName, ranks: Sequence[int], *,
                     root: int) -> PyTree:
    """Broadcast ``root``'s value to the replicas in ``ranks`` only; others
    keep their input (Horovod ``broadcast(..., process_set=...)``)."""
    bound = _bound_axes(axis)
    if not bound:
        return x
    sized = _sized_axes(bound)
    if not sized:
        return jax.tree.map(lambda t: _clear_unit_axes(t, bound), x)
    if root not in set(int(r) for r in ranks):
        raise ValueError(f"root {root} is not a member of the process set "
                         f"{sorted(set(int(r) for r in ranks))}")
    _check_ranks(bound, ranks)
    m = _member_mask(bound, ranks)
    idx = _linear_index(bound)

    def _f(t):
        rooted = lax.psum(
            jnp.where(idx == root, _vary_over(t, sized), jnp.zeros_like(t)),
            sized)
        return _clear_unit_axes(jnp.where(m, rooted, t), bound)

    return jax.tree.map(_f, x)


def global_norm(tree: PyTree, axis: AxisName | None = None) -> jax.Array:
    """L2 norm of a pytree; if ``axis`` given, the norm of the *global*
    (allreduced) gradient — used by grad-clipping parity with the reference's
    pre-allreduce clipping semantics."""
    sq = sum(jnp.sum(jnp.square(t)) for t in jax.tree.leaves(tree))
    if axis is not None and _in_mapped_context(axis):
        sq = lax.psum(sq, axis)
    return jnp.sqrt(sq)
