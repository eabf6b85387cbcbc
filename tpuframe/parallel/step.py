"""Compiled SPMD train/eval steps — the TPU-native hot loop.

Reference hot loop (SURVEY.md §4.1): forward → backward with per-grad hooks
enqueueing async NCCL allreduces into Horovod's C++ op queue → fusion →
``opt.step()`` waits on handles.  On TPU the whole step is ONE XLA program:
grads are ``pmean``-ed inside the traced function, and the compiler does the
ordering, fusion (all-reduce combining) and compute/communication overlap
that Horovod's runtime did by hand.  The only per-step host work left is
feeding the next sharded batch (``tpuframe.data``) and reading back metrics —
exactly the mapping called out in SURVEY.md §2 (L1 row).

Two step-construction modes:
  - ``shard_map`` (default): explicit per-shard code + explicit ``pmean`` —
    the closest analog of Horovod's explicit allreduce, with no surprises.
  - ``jit`` (auto-SPMD): sharding propagation inserts the collectives; same
    semantics, exercised in tests to cross-check the explicit path.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpuframe.parallel import mesh as mesh_lib

_shard_map = jax.shard_map

PyTree = Any

# loss_fn(params, model_state, batch, rng) -> (loss, (new_model_state, metrics))
LossFn = Callable[[PyTree, PyTree, PyTree, jax.Array], tuple[jax.Array, tuple[PyTree, dict]]]


@jax.tree_util.register_dataclass
@dataclass
class TrainState:
    """Replicated training state. ``model_state`` carries mutable collections
    (BatchNorm statistics for the ResNets); empty dict for stateless models."""

    step: jax.Array
    params: PyTree
    opt_state: PyTree
    model_state: PyTree
    rng: jax.Array

    @classmethod
    def create(cls, params: PyTree, tx: optax.GradientTransformation,
               model_state: PyTree | None = None, rng: jax.Array | None = None):
        return cls(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
            model_state={} if model_state is None else model_state,
            rng=jax.random.key(0) if rng is None else rng,
        )


def _grad_step(loss_fn: LossFn, tx: optax.GradientTransformation,
               axes: tuple[str, ...] | None,
               fusion_threshold: int | None,
               accum_steps: int,
               grad_reduce: str,
               weight_update: str,
               hier: str,
               state: TrainState, batch: PyTree):
    """Shared body for both modes. ``axes`` bound ⇒ explicit collectives."""
    step_rng = jax.random.fold_in(state.rng, state.step)
    if axes:
        # Decorrelate per-replica dropout while keeping params in lockstep.
        for ax in axes:
            step_rng = jax.random.fold_in(step_rng, lax.axis_index(ax))

    if accum_steps > 1:
        return _accum_grad_step(loss_fn, tx, axes, fusion_threshold,
                                accum_steps, grad_reduce, weight_update,
                                hier, state, batch, step_rng)

    # The reference's raison d'être: synchronous gradient averaging.
    # Horovod: per-tensor async NCCL ring-allreduce with fusion buffer.
    # Here: the *global* (pmean-ed) loss is what gets differentiated, so the
    # autodiff transpose emits the cross-replica reduction of the gradients
    # (params are replicated/unvarying, so d(pmean ℓ)/dθ = psum(∂ℓᵢ/∂θ)/N —
    # exactly Horovod's averaged allreduce).  XLA's all-reduce combiner fuses
    # the per-leaf reductions and the scheduler overlaps them with remaining
    # backward compute (SURVEY.md §3b).
    #
    # ``fusion_threshold`` set (TPUFRAME_FUSION_THRESHOLD) selects the
    # explicit Horovod-parity path instead: params are pcast to per-replica
    # ("varying") so the backward produces LOCAL gradients with NO implicit
    # reduction (the transpose of replicated params would otherwise insert
    # its own psum), and the framework's fusion buffers
    # (tpuframe.parallel.fusion) perform the only cross-replica averaging —
    # one psum per ≤threshold-byte bucket, 0 → one per leaf.  Same math
    # (psum is linear); observable in the compiled HLO's all-reduce count.
    # ``grad_reduce="adasum"`` also needs LOCAL per-replica grads — the
    # adaptive combine is computed from them, so the implicit
    # pmean-of-loss transpose (which pre-averages) cannot be used.
    explicit = bool(axes) and (fusion_threshold is not None
                               or grad_reduce == "adasum")
    # ZeRO-1 weight-update sharding consumes LOCAL grads too: the sharded
    # update's reduce-scatter IS the step's gradient reduction, so the
    # implicit pmean-of-loss transpose (which would all-reduce) must not
    # run: the params are pcast varying like the explicit path.
    zero1 = bool(axes) and weight_update == "zero1"
    # The two-level (hierarchical) lowering restructures the gradient
    # mean itself — rs over ICI → cross-slice mean over DCN → ag back
    # (tpuframe.parallel.hier) — so it consumes LOCAL grads like every
    # other explicit wire pattern.  The zero1 tail runs its own
    # two-stage scatter/gather and already takes local grads.
    hier_local = bool(axes) and hier == "hier" and not zero1
    local_grads = explicit or zero1 or hier_local
    diff_params = state.params
    if local_grads:
        diff_params = jax.tree.map(
            lambda p: lax.pcast(p, axes, to="varying"), state.params)

    def global_loss(params, model_state, batch, rng):
        loss, aux = loss_fn(params, model_state, batch, rng)
        if axes and not local_grads:
            loss = lax.pmean(loss, axes)
        return loss, aux

    (loss, (model_state, metrics)), grads = jax.value_and_grad(
        global_loss, has_aux=True)(diff_params, state.model_state, batch, step_rng)

    return _reduce_and_apply(tx, axes, fusion_threshold, grad_reduce,
                             weight_update, hier, state,
                             grads, loss, metrics, model_state,
                             reduce_grads=local_grads)


def _reduce_and_apply(tx, axes, fusion_threshold, grad_reduce, weight_update,
                      hier, state, grads, loss, metrics, model_state, *,
                      reduce_grads: bool):
    """Shared step tail: cross-replica reductions + optimizer update.

    ``reduce_grads``: True when ``grads``/``loss`` are still per-replica
    (explicit-fusion, adasum, zero1, two-level and accumulation
    paths); False when the pmean-of-loss transpose already reduced them
    (the implicit default)."""
    if weight_update == "zero1" and axes:
        # ZeRO-1 tail: NO gradient all-reduce — the grads stay local and
        # zero1.sharded_update's reduce-scatter performs the one and only
        # gradient-sized reduction.  Scalars (loss/metrics) and BN stats
        # still pmean (all under the audit's scalar floor).
        # ``fusion_threshold`` buckets that reduce-scatter (and the param
        # all-gather out) — same padded bytes, n_buckets collectives
        # instead of n_leaves, issued before any shard is consumed.
        from tpuframe.parallel import zero1 as zero1_lib

        if reduce_grads:
            loss = lax.pmean(loss, axes)
        metrics = jax.tree.map(lambda m: lax.pmean(m, axes), metrics)
        model_state = jax.tree.map(lambda s: lax.pmean(s, axes), model_state)
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads,
                             state.params)
        params, opt_state, grad_norm = zero1_lib.sharded_update(
            tx, axes, state.params, state.opt_state, grads,
            fusion_threshold=fusion_threshold, hier=(hier == "hier"))
        metrics = dict(metrics)
        metrics["loss"] = loss
        metrics["grad_norm"] = grad_norm
        return TrainState(step=state.step + 1, params=params,
                          opt_state=opt_state, model_state=model_state,
                          rng=state.rng), metrics
    if reduce_grads and axes:
        if grad_reduce == "adasum":
            from tpuframe.parallel import collectives

            grads = collectives.adasum(grads, axes)
        elif hier == "hier":
            # Two-level cross-slice mean (tpuframe.parallel.hier): full
            # bytes stay on ICI, only the 1/n_inner shard crosses DCN.
            # fusion_threshold buckets the lowerings.
            from tpuframe.parallel import hier as hier_lib

            if fusion_threshold is not None:
                grads = hier_lib.fused_hier_mean(
                    grads, axes, threshold_bytes=fusion_threshold)
            else:
                grads = hier_lib.hier_mean(grads, axes)
        elif fusion_threshold is not None:
            from tpuframe.parallel import fusion

            grads = fusion.staged_pmean(grads, axes,
                                        threshold_bytes=fusion_threshold)
        else:
            grads = jax.tree.map(lambda g: lax.pmean(g, axes), grads)
        loss = lax.pmean(loss, axes)
    if axes:
        metrics = jax.tree.map(lambda m: lax.pmean(m, axes), metrics)
        # BatchNorm running stats: cross-replica averaged so the replicated
        # state stays single-valued (reference kept per-GPU local stats and
        # checkpointed rank 0's — averaging is the SPMD-correct equivalent).
        model_state = jax.tree.map(lambda s: lax.pmean(s, axes), model_state)

    # No-op for same-dtype grads; the accumulation path accumulates in f32
    # and casts back to the param dtype here.
    grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, state.params)
    updates, opt_state = tx.update(grads, state.opt_state, state.params)
    params = optax.apply_updates(state.params, updates)
    metrics = dict(metrics)
    metrics["loss"] = loss
    metrics["grad_norm"] = optax.global_norm(grads)
    new_state = TrainState(step=state.step + 1, params=params,
                           opt_state=opt_state, model_state=model_state,
                           rng=state.rng)
    return new_state, metrics


def _accum_grad_step(loss_fn, tx, axes, fusion_threshold, accum_steps,
                     grad_reduce, weight_update, hier, state, batch,
                     step_rng):
    """Gradient accumulation — Horovod's ``backward_passes_per_step``
    (DistributedOptimizer option; the reference's recipe for batches that
    exceed device memory).  The local batch is split into ``accum_steps``
    microbatches, a ``lax.scan`` runs fwd+bwd per microbatch accumulating
    f32 gradients and threading mutable model state (BN stats update
    sequentially, matching N torch backward passes), and ONE optimizer
    update + ONE cross-replica reduction happens at the end — collectives
    per step stay constant as accum grows, exactly Horovod's semantics."""
    for leaf in jax.tree.leaves(batch):
        if leaf.shape[0] % accum_steps:
            raise ValueError(
                f"accum_steps={accum_steps} does not divide the per-device "
                f"batch {leaf.shape[0]} (leaf shape {leaf.shape}); choose a "
                f"global batch divisible by devices x accum_steps")
    micro = jax.tree.map(
        lambda a: a.reshape(accum_steps, a.shape[0] // accum_steps,
                            *a.shape[1:]), batch)

    # Differentiate w.r.t. per-replica ("varying") copies of the params:
    # grads then stay LOCAL through the whole scan — zero collectives per
    # microbatch — and the single reduction below is the step's only one
    # (Horovod's backward_passes_per_step wire behavior).  Grads of
    # replicated params would instead be psum'd inside every scan
    # iteration by the autodiff transpose.
    def vary(t):
        if not axes:
            return t
        return jax.tree.map(
            lambda a: a if all(x in jax.typeof(a).vma for x in axes)
            else lax.pcast(a, tuple(x for x in axes
                                    if x not in jax.typeof(a).vma),
                           to="varying"), t)

    diff_params = vary(state.params)

    def one_micro(carry, xs):
        mb_i, i = xs
        model_state, g_acc, loss_acc, metrics_acc = carry
        rng_i = jax.random.fold_in(step_rng, i)
        (loss, (model_state, metrics)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(diff_params, model_state, mb_i, rng_i)
        g_acc = jax.tree.map(
            lambda a, b: a + b.astype(jnp.float32), g_acc, g)
        metrics_acc = jax.tree.map(jnp.add, metrics_acc,
                                   jax.tree.map(jnp.asarray, dict(metrics)))
        return (model_state, g_acc, loss_acc + loss, metrics_acc), None

    zeros_like_f32 = vary(jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), state.params))
    mb0 = jax.tree.map(lambda a: a[0], micro)
    _, (_, metrics0) = jax.eval_shape(
        lambda: loss_fn(state.params, state.model_state, mb0, step_rng))
    metrics_zero = vary(jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), dict(metrics0)))
    (model_state, grads, loss, metrics), _ = lax.scan(
        one_micro,
        (vary(state.model_state), zeros_like_f32,
         vary(jnp.zeros((), jnp.float32)), metrics_zero),
        (micro, jnp.arange(accum_steps)))
    grads = jax.tree.map(lambda g: g / accum_steps, grads)
    loss = loss / accum_steps
    metrics = jax.tree.map(lambda m: m / accum_steps, metrics)

    return _reduce_and_apply(tx, axes, fusion_threshold, grad_reduce,
                             weight_update, hier, state,
                             grads, loss, metrics, model_state,
                             reduce_grads=True)


def make_train_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    mesh: Mesh | None = None,
    *,
    mode: str = "shard_map",
    donate: bool = True,
    batch_partition: P | None = None,
    reduce_axes: tuple[str, ...] | None = None,
    state_shardings: PyTree | None = None,
    fusion_threshold: int | None = None,
    accum_steps: int = 1,
    grad_reduce: str = "mean",
    compiler_options: dict | None = None,
    remat_policy: str | None = None,
    weight_update: str = "replicated",
    hier: str = "flat",
):
    """Build the compiled train step.

    ``grad_reduce``: ``"mean"`` (default — Horovod's averaged allreduce) or
    ``"adasum"`` (adaptive summation, Horovod's ``op=hvd.Adasum``): local
    per-replica gradients are combined with the scale-insensitive ppermute
    butterfly (tpuframe.parallel.collectives.adasum) instead of averaged.
    With adasum, keep ``scale_lr_by_batch`` off — removing the LR-by-size
    rule is the op's purpose.  shard_map mode only; composes with
    ``accum_steps`` (local f32 accumulation, one adasum at the end) but not
    with ``fusion_threshold`` (the butterfly is its own wire pattern).

    ``fusion_threshold``: byte size of the explicit gradient-fusion buffers
    (HOROVOD_FUSION_THRESHOLD parity, tpuframe.parallel.fusion); ``None``
    (default) leaves gradient reduction to the autodiff transpose + XLA's
    combiner.  Only meaningful in ``shard_map`` mode — auto-SPMD programs
    have no explicit collectives to pack.

    ``accum_steps``: gradient accumulation (Horovod's
    ``backward_passes_per_step``): the per-device batch is split into this
    many microbatches scanned sequentially, f32 grad accumulation, one
    optimizer update and one cross-replica reduction per step.  NOTE the
    batching direction differs from Horovod: Horovod aggregates N loader
    batches (effective batch grows Nx); here the configured batch is SPLIT
    (effective batch unchanged, per-pass memory shrinks Nx) — to port a
    Horovod recipe, multiply global_batch by N as well.

    ``batch_partition``/``reduce_axes``: sequence-parallel configs pass
    ``P(('data','fsdp'), 'seq')`` and ``('data','fsdp','seq')`` so batches
    shard along their sequence dim and the loss mean spans the seq axis.
    A non-default ``batch_partition`` applies to every batch leaf, so all
    leaves must share the partitioned ranks.

    ``state_shardings``: a NamedSharding tree over the TrainState (see
    tpuframe.parallel.fsdp) — selects the auto-SPMD ``jit`` mode with
    parameters/optimizer state sharded; XLA inserts the all-gathers and
    reduce-scatters of ZeRO-style training.

    ``mesh=None`` → single-device jit (config 1, SURVEY.md §7 step 1): same
    body, no collectives — the property the reference gets from Horovod's
    size()==1 no-op mode.

    ``remat_policy``: a :mod:`tpuframe.mem` policy name (``none`` /
    ``full`` / ``per_block`` / ``dots`` / ``save_named(...)``) applied to
    ``loss_fn`` before differentiation — selects which forward
    activations are saved for the backward (the §6 HBM-traffic lever).
    ``None``/``"none"`` leaves the loss unwrapped.  Resolution (env >
    tuning DB > default) is the caller's job via ``mem.resolve``.

    ``weight_update``: ``"replicated"`` (default — every chip holds the
    full optimizer state and applies the full update) or ``"zero1"``
    (:mod:`tpuframe.parallel.zero1`, arXiv:2004.13336): the gradient
    all-reduce is replaced by reduce-scatter → 1/n-shard optimizer
    update → tiled all-gather, and the optimizer state lives sharded
    (build it with ``zero1.make_state``; ``TrainState.create``'s
    replicated layout is rejected at trace time).  shard_map mode with a
    mesh only; element-wise optimizers only; composes with
    ``fusion_threshold`` (the sharded update's reduce-scatter/all-gather
    go bucketed — same padded bytes, fewer collectives, issued before
    any shard is consumed) but not with ``adasum`` (an all-gradient wire
    pattern the sharded update replaces) or ``state_shardings``
    (auto-SPMD ZeRO-3 already shards the update).  Resolution (env
    ``TPUFRAME_WEIGHT_UPDATE`` > tuning DB > default) is the caller's job
    via ``zero1.resolve``.

    ``hier``: ``"flat"`` (default — cross-replica means are single
    collectives whose groups may span slices) or ``"hier"``
    (:mod:`tpuframe.parallel.hier`, arXiv:1909.09756): the gradient mean
    lowers as in-slice reduce-scatter over ICI → cross-slice mean of the
    1/n_inner shard over DCN → in-slice all-gather back, so only
    1/n_inner of the gradient bytes touch the ~32x-slower fabric.  On a
    single-slice mesh the lowering degenerates to flat.  shard_map mode
    with a mesh only; composes with ``accum_steps``, ``weight_update=
    'zero1'`` (the sharded update's scatter/gather go two-stage) and
    ``fusion_threshold`` (bucketed lowerings), but not with ``adasum``
    (its butterfly is its own wire pattern).  Resolution (env
    ``TPUFRAME_HIER`` > tuning DB > default) is the caller's job via
    ``hier.resolve``.
    """
    from tpuframe.parallel import hier as hier_lib

    hier = hier_lib.validate_mode(hier)
    if hier == "hier":
        if state_shardings is not None or mode != "shard_map":
            raise ValueError("hier='hier' needs shard_map mode — auto-SPMD "
                             "programs have no explicit collectives to "
                             "restructure")
        if grad_reduce == "adasum":
            raise ValueError("hier='hier' does not compose with adasum — "
                             "the butterfly is its own wire pattern")
    weight_update = (weight_update or "replicated").strip().lower()
    if weight_update not in ("replicated", "zero1"):
        raise ValueError(f"unknown weight_update {weight_update!r}; "
                         f"expected 'replicated' or 'zero1'")
    if weight_update == "zero1":
        if mesh is None:
            raise ValueError("weight_update='zero1' needs a mesh — a world "
                             "of 1 has nothing to shard the update over")
        if state_shardings is not None:
            raise ValueError("weight_update='zero1' is the shard_map DP "
                             "path; state_shardings (auto-SPMD ZeRO-3) "
                             "already shards the update")
        if grad_reduce == "adasum":
            raise ValueError("weight_update='zero1' does not compose with "
                             "adasum — the butterfly needs full gradients "
                             "on every replica")
        if mode != "shard_map":
            raise ValueError("weight_update='zero1' needs shard_map mode")
    if remat_policy:
        from tpuframe.mem import policy as mem_policy

        loss_fn = mem_policy.wrap(loss_fn, remat_policy)
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if grad_reduce not in ("mean", "adasum"):
        raise ValueError(f"grad_reduce must be 'mean' or 'adasum', "
                         f"got {grad_reduce!r}")
    if grad_reduce == "adasum" and fusion_threshold is not None:
        raise ValueError("grad_reduce='adasum' does not compose with "
                         "fusion_threshold — the butterfly is its own wire "
                         "pattern")
    if mesh is None:
        # World of 1: adasum degrades to identity like every collective.
        body = functools.partial(_grad_step, loss_fn, tx, None, None,
                                 accum_steps, "mean", "replicated", "flat")
        return jax.jit(body, donate_argnums=(0,) if donate else (),
                       compiler_options=compiler_options)

    # Reduce over every batch-like axis, including size-1 ones: a size-1 pmean
    # is free after compilation but tells shard_map's replication checker the
    # outputs are single-valued across those axes.  Sequence-parallel configs
    # extend both: the batch is additionally sharded along its seq dim and the
    # loss mean spans the seq axis too.
    axes = reduce_axes if reduce_axes is not None else mesh_lib.batch_axes(mesh)
    repl = NamedSharding(mesh, P())
    batch_part = (batch_partition if batch_partition is not None
                  else mesh_lib.batch_spec(mesh=mesh))
    batch_sh = NamedSharding(mesh, batch_part)

    if state_shardings is not None:
        mode = "jit"  # sharded state is an auto-SPMD placement decision
        # All shardings of one program must live on one mesh: the state's.
        any_leaf = jax.tree.leaves(state_shardings)[0]
        repl = NamedSharding(any_leaf.mesh, P())
        batch_sh = NamedSharding(any_leaf.mesh, batch_part)
    if mode == "jit":
        if grad_reduce != "mean":
            raise ValueError("grad_reduce='adasum' needs shard_map mode — "
                             "auto-SPMD has no per-replica grads to combine")
        # Auto-SPMD: annotate shardings, let the partitioner insert collectives.
        body = functools.partial(_grad_step, loss_fn, tx, None, None,
                                 accum_steps, "mean", "replicated", "flat")
        state_sh = repl if state_shardings is None else state_shardings
        return jax.jit(
            body,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, repl),
            donate_argnums=(0,) if donate else (),
            compiler_options=compiler_options,
        )

    if mode != "shard_map":
        raise ValueError(f"unknown step mode {mode!r}")

    body = functools.partial(_grad_step, loss_fn, tx, axes, fusion_threshold,
                             accum_steps, grad_reduce, weight_update, hier)
    if weight_update == "zero1":
        from tpuframe.parallel import zero1 as zero1_lib

        n_shards = zero1_lib.world_size(mesh, axes)

        def zero1_stepper(state, batch):
            # The opt_state tree shape is the optimizer's business
            # (tx.init), only known from the traced state — so the
            # per-leaf spec tree (moment vectors sharded on dim 0,
            # everything else replicated) is built here inside the jit
            # trace.  shard_map composes under jit, and ``.lower()``
            # still works for the AOT sweeps/audits.
            zero1_lib.check_state_layout(state, n_shards)
            specs = zero1_lib.state_partition_specs(state, axes)
            mapped = _shard_map(body, mesh=mesh,
                                in_specs=(specs, batch_part),
                                out_specs=(specs, P()))
            return mapped(state, batch)

        return jax.jit(zero1_stepper,
                       donate_argnums=(0,) if donate else (),
                       compiler_options=compiler_options)
    mapped = _shard_map(
        body, mesh=mesh,
        in_specs=(P(), batch_part),
        out_specs=(P(), P()),
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else (),
                   compiler_options=compiler_options)


def make_eval_step(
    metric_fn: Callable[[PyTree, PyTree, PyTree], dict],
    mesh: Mesh | None = None,
    *,
    batch_partition: P | None = None,
    reduce_axes: tuple[str, ...] | None = None,
    state_shardings: PyTree | None = None,
):
    """Forward-only step with cross-replica metric averaging.

    Reference parity: eval loop + one small ``hvd.allreduce`` per metric
    (SURVEY.md §4.5).  ``metric_fn(params, model_state, batch) -> dict`` must
    return *mean-able* values (sums should be divided locally; weights equal).
    """
    if mesh is None:
        return jax.jit(lambda s, b: metric_fn(s.params, s.model_state, b))

    axes = reduce_axes if reduce_axes is not None else mesh_lib.batch_axes(mesh)
    batch_part = (batch_partition if batch_partition is not None
                  else mesh_lib.batch_spec(mesh=mesh))

    if state_shardings is not None:
        # Auto-SPMD eval against fsdp-sharded state (shard_map would demand a
        # replicated state); means over the sharded batch become global
        # reductions via sharding propagation.
        amesh = jax.tree.leaves(state_shardings)[0].mesh
        return jax.jit(
            lambda s, b: metric_fn(s.params, s.model_state, b),
            in_shardings=(state_shardings, NamedSharding(amesh, batch_part)),
            out_shardings=NamedSharding(amesh, P()),
        )

    def body(state: TrainState, batch: PyTree) -> dict:
        metrics = metric_fn(state.params, state.model_state, batch)
        return jax.tree.map(lambda m: lax.pmean(m, axes), metrics)

    mapped = _shard_map(
        body, mesh=mesh,
        in_specs=(P(), batch_part),
        out_specs=P(),
    )
    return jax.jit(mapped)


def replicate_state(state: TrainState, mesh: Mesh) -> TrainState:
    """Place state replicated on the mesh (reference parity with the rank-0
    ``broadcast_parameters`` at startup, SURVEY.md §4.1 — under SPMD this is a
    device_put with a replicated sharding, no network broadcast needed)."""
    repl = mesh_lib.replicated_sharding(mesh)
    return jax.tree.map(lambda t: mesh_lib.host_device_put(t, repl), state)
