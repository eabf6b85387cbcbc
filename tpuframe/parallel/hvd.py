"""``tpuframe.parallel.hvd`` — a Horovod-compatible facade.

The reference's entire distributed API surface is the handful of
``horovod.torch`` calls named in SURVEY.md §3a "Distributed glue":

    hvd.init(); hvd.size(); hvd.rank(); hvd.local_rank()
    hvd.allreduce(t, average=True)
    hvd.broadcast_parameters(state_dict, root_rank=0)
    hvd.broadcast_optimizer_state(opt, root_rank=0)
    opt = hvd.DistributedOptimizer(opt, named_parameters=...)

This module provides the same verbs with TPU-native semantics so a reference
user can port ``train.py`` mechanically.  The key semantic shift: Horovod has
one rank space (one process per GPU); SPMD JAX has two. ``size()`` is the
GLOBAL CHIP COUNT — the LR-scaling denominator, Horovod's ``hvd.size()``
equivalent. ``rank()`` is the HOST/process index — use it only for
rank-0-gated logging and per-host data sharding (pair it with
``jax.process_count()``, not ``size()``). The per-chip rank inside a step
function is the mesh position bound by ``shard_map`` (``lax.axis_index``).

``DistributedOptimizer`` wraps an optax GradientTransformation and performs
the gradient averaging Horovod did in its C++ runtime — but as a traced
``pmean`` that XLA fuses/overlaps (SURVEY.md §2 L1 mapping).  When the step is
not mapped (config 1, single process), it is the identity wrapper, matching
``hvd``'s behavior with size()==1.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import optax

from tpuframe.parallel import bootstrap, collectives
from tpuframe.parallel import mesh as mesh_lib

PyTree = Any

_DEFAULT_AXIS = mesh_lib.BATCH_AXES  # grads reduce over all batch-like axes


def init(config: bootstrap.DistConfig | None = None) -> None:
    """Reference parity: ``hvd.init()`` (SURVEY.md §4.3)."""
    bootstrap.initialize(config)


def size() -> int:
    """Global device count — the LR-scaling denominator the reference uses
    (``scale LR by hvd.size()``, SURVEY.md §3a)."""
    return jax.device_count()


def rank() -> int:
    """Host/process index — NOT the chip index; pair with
    ``jax.process_count()`` for host-level sharding. Per-chip rank inside a
    step fn is ``lax.axis_index``."""
    return jax.process_index()


def local_rank() -> int:
    """Reference used this to pin a GPU; on TPU device pinning is automatic,
    kept for port compatibility (always 0 within a host's first device)."""
    return 0


def local_size() -> int:
    return jax.local_device_count()


def is_primary() -> bool:
    return bootstrap.is_primary()


class _ReduceOp:
    """Reduction-op sentinel, mirroring ``horovod.torch``'s op constants."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def __repr__(self):
        return f"hvd.{self.name}"


Average = _ReduceOp("Average")
Sum = _ReduceOp("Sum")
Adasum = _ReduceOp("Adasum")
Min = _ReduceOp("Min")
Max = _ReduceOp("Max")
Product = _ReduceOp("Product")


class ProcessSet:
    """Subgroup for collectives (Horovod ``hvd.ProcessSet``).

    Horovod builds a sub-communicator per set; under SPMD the set is a
    static membership list over the linearized replica index, and the
    collective is a masked full-axis reduction (non-members keep their
    input untouched, matching Horovod's "op never runs outside the set").
    """

    def __init__(self, ranks):
        ranks = tuple(sorted(set(int(r) for r in ranks)))
        if not ranks:
            raise ValueError("ProcessSet needs at least one rank")
        if any(r < 0 for r in ranks):
            raise ValueError(f"negative rank in ProcessSet: {ranks}")
        self.ranks = ranks

    def size(self) -> int:
        return len(self.ranks)

    def __repr__(self):
        return f"ProcessSet(ranks={list(self.ranks)})"


def allreduce(tensor: PyTree, average: bool | None = None,
              name: str | None = None, axis=_DEFAULT_AXIS,
              op: _ReduceOp | None = None,
              process_set: ProcessSet | None = None) -> PyTree:
    """``hvd.allreduce`` — inside a mapped step fn this is a traced collective;
    outside, identity (single-host value already global under SPMD).

    ``op`` selects the reduction (``hvd.Average`` default / ``Sum`` /
    ``Adasum`` / ``Min`` / ``Max`` / ``Product``); the legacy ``average=``
    boolean is honored but, as in Horovod, may not be combined with ``op``.
    ``process_set`` restricts the op to a replica subgroup — members get the
    subgroup result, non-members keep their input.
    """
    del name  # Horovod used names for its fusion table; XLA needs none.
    if average is not None and op is not None:
        raise ValueError("specify either average= or op=, not both "
                         "(Horovod raises here too)")
    if op is None:
        op = Sum if average is False else Average
    if process_set is not None:
        if op is Average or op is Sum:
            return collectives.masked_allreduce(
                tensor, axis, process_set.ranks, average=op is Average)
        raise NotImplementedError(
            f"process_set is supported for Average/Sum, not {op!r}")
    if op is Average:
        return collectives.allreduce(tensor, axis=axis, average=True)
    if op is Sum:
        return collectives.allreduce(tensor, axis=axis, average=False)
    if op is Adasum:
        return collectives.adasum(tensor, axis=axis)
    if op is Min:
        return collectives.reduce_min(tensor, axis=axis)
    if op is Max:
        return collectives.reduce_max(tensor, axis=axis)
    if op is Product:
        return collectives.reduce_prod(tensor, axis=axis)
    raise ValueError(f"unknown reduction op {op!r}")


def broadcast_parameters(params: PyTree, root_rank: int = 0, axis=_DEFAULT_AXIS,
                         process_set: ProcessSet | None = None) -> PyTree:
    """``hvd.broadcast_parameters`` — under SPMD initialization, parameters are
    created identically on every chip from a shared PRNG key, so the broadcast
    is only needed when a caller deliberately diverged state; we honor the
    call inside mapped contexts and no-op otherwise."""
    if process_set is not None:
        return collectives.masked_broadcast(params, axis, process_set.ranks,
                                            root=root_rank)
    return collectives.broadcast(params, axis=axis, root=root_rank)


def broadcast_optimizer_state(opt_state: PyTree, root_rank: int = 0,
                              axis=_DEFAULT_AXIS) -> PyTree:
    return collectives.broadcast(opt_state, axis=axis, root=root_rank)


class Compression:
    """Horovod's ``hvd.Compression`` namespace: scripts pass
    ``compression=hvd.Compression.fp16`` — map the members onto
    ``DistributedOptimizer``'s string knob (fp16 → bf16, the TPU-native
    half precision; see the compression docs below)."""

    none = None
    fp16 = "bf16"


class _DistState(NamedTuple):
    inner: Any


def DistributedOptimizer(
    tx: optax.GradientTransformation,
    *,
    axis=_DEFAULT_AXIS,
    average: bool | None = None,
    compression: str | None = None,
    op: _ReduceOp | None = None,
) -> optax.GradientTransformation:
    """Wrap ``tx`` so updates see cross-replica-averaged gradients.

    Reference parity: ``hvd.DistributedOptimizer`` hooks ``loss.backward()``'s
    per-grad callbacks to enqueue async fused NCCL allreduces and waits in
    ``opt.step()`` (SURVEY.md §4.1 hot loop).  Under XLA the entire step is one
    program: the ``pmean`` below is scheduled/overlapped with backward compute
    by the compiler, which is the same overlap Horovod implements by hand.

    ``compression``: None or "bf16", which mirrors Horovod's fp16
    gradient compression (cast down for the wire, restored after
    reduction).

    ``op=hvd.Adasum`` selects adaptive summation (collectives.adasum) in
    place of the mean — Horovod's scale-insensitive large-batch reduction.
    Adasum's combine is norm-based, so wire compression is disallowed with
    it (as in Horovod, where Adasum + fp16 compression is unsupported).
    """
    if average is not None and op is not None:
        raise ValueError("specify either average= or op=, not both "
                         "(same contract as hvd.allreduce)")
    if op is None:
        op = Sum if average is False else Average
    if op not in (Average, Sum, Adasum):
        raise ValueError(f"DistributedOptimizer supports Average/Sum/Adasum, "
                         f"got {op!r}")
    if op is Adasum and compression is not None:
        raise ValueError("Adasum's norm-based combine does not compose with "
                         "wire compression")
    average = op is Average

    def init_fn(params):
        return _DistState(inner=tx.init(params))

    def update_fn(grads, state, params=None, **extra):
        if op is Adasum:
            updates, inner = tx.update(
                collectives.adasum(grads, axis=axis), state.inner, params,
                **extra)
            return updates, _DistState(inner=inner)
        grads, orig_dtypes = _maybe_compress(grads, compression)
        # vma-aware: reduces varying leaves, passes through already-psum'd
        # ones (gradients of replicated params arrive pre-summed under jax's
        # shard_map autodiff) — see collectives.average_gradients.
        if average:
            grads = collectives.average_gradients(grads, axis=axis)
        else:
            grads = collectives.sum_gradients(grads, axis=axis)
        grads = _maybe_decompress(grads, orig_dtypes)
        updates, inner = tx.update(grads, state.inner, params, **extra)
        return updates, _DistState(inner=inner)

    return optax.GradientTransformation(init_fn, update_fn)


def allgather(tensor, name: str | None = None, axis=_DEFAULT_AXIS):
    """``hvd.allgather`` — concatenate per-replica tensors along dim 0."""
    del name
    return collectives.allgather(tensor, axis=axis)


def alltoall(tensor, splits=None, name: str | None = None,
             axis=_DEFAULT_AXIS):
    """``hvd.alltoall`` with equal splits (dim 0 scattered, gathered back).
    Horovod's ragged ``splits`` have no XLA equivalent — static shapes are
    the compilation model; pre-pad to equal splits instead."""
    del name
    if splits is not None:
        uniform = len({int(x) for x in splits}) == 1
        if not uniform or sum(int(x) for x in splits) != tensor.shape[0]:
            raise NotImplementedError(
                "alltoall with UNEQUAL splits is ragged; XLA collectives "
                "are static-shape — pad to equal splits")
        # equal splits covering dim 0 == exactly the static case
    return collectives.alltoall(tensor, axis=axis)


def grouped_allreduce(tensors, average: bool = True, name: str | None = None,
                      axis=_DEFAULT_AXIS):
    """``hvd.grouped_allreduce`` — one fused reduction for a list of
    tensors.  Horovod groups to control its fusion buffer; XLA's combiner
    fuses adjacent reductions regardless, so this is allreduce mapped over
    the list (the group arrives at the wire fused either way)."""
    del name
    return [collectives.allreduce(t, axis=axis, average=average)
            for t in tensors]


def barrier() -> None:
    """``hvd.barrier`` — host-level process barrier (checkpoint/teardown
    sync; NOT needed around compiled steps, which order themselves)."""
    bootstrap.host_barrier("tpuframe_hvd_barrier")


def join() -> int:
    """``hvd.join`` — Horovod's elastic straggler drain.  tpuframe's
    failure model is slice-restart + checkpoint resume (SURVEY.md §5.3):
    pods fail as a unit, so there is no partial-membership state to drain.
    Provided as a host barrier for porting compatibility; returns -1 like
    Horovod does when no rank is joining."""
    barrier()
    return -1


def shutdown() -> None:
    """``hvd.shutdown`` — tear down the distributed runtime (idempotent:
    bootstrap tracks init state, so a later ``hvd.init()`` re-initializes
    and the launcher's own clean-exit shutdown doesn't double-teardown)."""
    bootstrap.shutdown()


def allreduce_async_(tensor: PyTree, average: bool | None = None,
                     name: str | None = None, axis=_DEFAULT_AXIS,
                     op: _ReduceOp | None = None,
                     process_set: ProcessSet | None = None) -> PyTree:
    """``hvd.allreduce_async_`` — returns a "handle" to pass to
    ``synchronize``.  Under XLA the handle IS the traced value: inside a
    compiled program every collective is already asynchronous until a
    consumer needs it (the scheduler overlaps it with compute — the
    overlap Horovod's handle API exists to expose), so the pair maps to
    allreduce + identity."""
    return allreduce(tensor, average=average, name=name, axis=axis, op=op,
                     process_set=process_set)


def synchronize(handle: PyTree) -> PyTree:
    """``hvd.synchronize`` — wait on an ``allreduce_async_`` handle.
    Inside jit: identity (tracers pass through — the data dependency is
    the synchronization).  Outside: blocks until the device value is
    ready, and surfaces any deferred execution error HERE, matching
    Horovod's semantics of synchronize being where failures appear."""
    if any(isinstance(leaf, jax.core.Tracer)
           for leaf in jax.tree.leaves(handle)):
        return handle
    return jax.block_until_ready(handle)


def mpi_built() -> bool:
    """Horovod build introspection.  tpuframe has no MPI dependency —
    bootstrap is jax.distributed's GRPC coordinator (SURVEY.md §4.3)."""
    return False


def nccl_built() -> bool:
    """No NCCL: collectives are XLA HLOs over ICI/DCN (SURVEY.md §3b)."""
    return False


def gloo_built() -> bool:
    """No Gloo: host-level rendezvous is the GRPC coordinator."""
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def broadcast_object(obj, root_rank: int = 0, name: str | None = None):
    """``hvd.broadcast_object`` — picklable host object from ``root_rank``
    to every process (collective; see bootstrap.broadcast_object)."""
    del name  # Horovod tags; no fusion table here
    return bootstrap.broadcast_object(obj, root=root_rank)


def allgather_object(obj, name: str | None = None) -> list:
    """``hvd.allgather_object`` — one picklable object per process,
    returned in process order everywhere."""
    del name
    return bootstrap.allgather_object(obj)


def _maybe_compress(grads: PyTree, compression: str | None):
    """Cast float32 leaves down for the reduction; returns the original
    dtypes so decompression restores exactly what arrived (bf16-native
    gradients stay bf16 throughout)."""
    if compression is None:
        return grads, None
    if compression == "bf16":
        import jax.numpy as jnp

        orig_dtypes = jax.tree.map(lambda g: g.dtype, grads)
        compressed = jax.tree.map(
            lambda g: g.astype(jnp.bfloat16) if g.dtype == jnp.float32 else g, grads
        )
        return compressed, orig_dtypes
    raise ValueError(f"unknown compression {compression!r}")


def _maybe_decompress(grads: PyTree, orig_dtypes: PyTree | None) -> PyTree:
    if orig_dtypes is None:
        return grads
    return jax.tree.map(lambda g, dt: g.astype(dt), grads, orig_dtypes)
