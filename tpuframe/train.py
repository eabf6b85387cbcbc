"""Training harness (L4) — the reference's ``train.py``, TPU-native.

Reference flow (SURVEY.md §4.1): init Horovod → pin GPU → build model/data/
optimizer → broadcast params → epoch loop with async allreduce hooks.
Here: bootstrap → mesh → compiled SPMD step → host loop that only feeds
sharded batches, logs, evals and checkpoints.

CLI:
    python -m tpuframe.train --config cifar10_resnet18 \
        [--set total_steps=100 --set global_batch=64] [--data-dir PATH] \
        [--ckpt-dir PATH]

Every workload config ([B:6–12]) runs through this one entry point, from
single-process MNIST to the multi-host pod launch (tpuframe.launch execs this
module on every worker).
"""

from __future__ import annotations

import argparse
import ast
import contextlib
import hashlib
import itertools
import os
import sys
import time
from dataclasses import dataclass
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from tpuframe import ckpt as ckpt_lib
from tpuframe import mem, models
from tpuframe.data import ShardedLoader, datasets
from tpuframe.models import losses
from tpuframe.obs import (Heartbeat, MetricLogger, RateMeter, StepTimeline,
                          parse_trace_steps, profile_trace,
                          start_profiler_server)
from tpuframe.obs import devmem as devmem_lib
from tpuframe.obs import events as events_lib
from tpuframe.obs import exporter as exporter_lib
from tpuframe.obs import flight as flight_lib
from tpuframe.obs import goodput as goodput_lib
from tpuframe.obs import metrics as obs_metrics
from tpuframe.obs import timeline as timeline_lib
from tpuframe.obs.timeline import span
from tpuframe.parallel import bootstrap
from tpuframe.resilience import faults as faults_lib
from tpuframe.resilience.preempt import RC_PREEMPTED, PreemptionGuard
from tpuframe.parallel import mesh as mesh_lib
from tpuframe.parallel import step as step_lib
from tpuframe.utils import build_optimizer, get_config
from tpuframe.utils.config import TrainConfig


def build_datasets(cfg: TrainConfig):
    builder = {
        "mnist": datasets.mnist,
        "cifar10": datasets.cifar10,
        "imagenet": datasets.imagenet,
        "glue_sst2": datasets.glue_sst2,
        "glue_mnli": datasets.glue_mnli,
        "glue_stsb": datasets.glue_stsb,
        "glue_cola": datasets.glue_cola,
        "lm_text": datasets.lm_text,
    }[cfg.dataset]
    return builder(cfg.data_dir, **cfg.dataset_kwargs)


def _is_text_task(cfg: TrainConfig) -> bool:
    return cfg.dataset in ("glue_sst2", "glue_mnli", "glue_stsb",
                           "glue_cola")


def _maybe_normalize(cfg: TrainConfig, x):
    """On-device normalization for uint8 image batches (datasets built
    with ``keep_u8=True``: 1 byte/px over the host→device link, 4x less
    host RAM).  XLA fuses this into the first conv's input read on TPU;
    on CPU hosts it lowers to the native FFI kernel
    (tpuframe.ops.native_call).  Float batches pass through — they were
    normalized on the host at build time."""
    if x.dtype != jnp.uint8:
        return x
    from tpuframe.ops.native_call import normalize_u8

    if cfg.data_dir is None:
        # Synthetic u8 is quantized [0,1]-scale data: de-quantize only, so
        # the u8 and f32 synthetic paths feed the same distribution.
        mean, std = np.float32(0.0), np.float32(1.0)
    else:
        # Real data: the same per-dataset constants the f32 builder branch
        # applies on the host.
        mean, std = {
            "imagenet": (datasets.IMAGENET_MEAN, datasets.IMAGENET_STD),
            "cifar10": (datasets.CIFAR_MEAN, datasets.CIFAR_STD),
        }.get(cfg.dataset, (np.float32(0.0), np.float32(1.0)))
    mean = np.broadcast_to(np.asarray(mean, np.float32), (x.shape[-1],))
    std = np.broadcast_to(np.asarray(std, np.float32), (x.shape[-1],))
    return normalize_u8(x, mean, std)


def _is_regression_task(cfg: TrainConfig) -> bool:
    # HF convention, enforced as stated: num_labels == 1 ⇒ regression
    # (STS-B) — MSE on the squeezed single logit, no accuracy metric.
    return cfg.model_kwargs.get("num_classes") == 1


def _is_lm_task(cfg: TrainConfig) -> bool:
    return cfg.dataset == "lm_text"


def _cfg_batch_axes(cfg: TrainConfig) -> tuple:
    """The config's data-parallel mesh axes — slice-aware: a multi-slice
    MeshSpec replicates data over the DCN ``slice`` axis too, so batch
    partitions and loss means must range over it (the mesh-aware
    ``mesh_lib.batch_axes`` twin, derivable before the mesh exists)."""
    if getattr(cfg.mesh, "slices", 1) > 1:
        return (mesh_lib.SLICE_AXIS, *mesh_lib.BATCH_AXES)
    return mesh_lib.BATCH_AXES


def _batch_layout(cfg: TrainConfig):
    """(loader partition, step batch_partition, reduce axes) for the config.
    Sequence-parallel configs shard the batch's seq dim and extend the loss
    mean over the seq axis; everything else uses the pure batch layout."""
    from jax.sharding import PartitionSpec as P
    if cfg.shard_seq:
        axes = _cfg_batch_axes(cfg)
        part = P(axes, "seq")
        return part, part, (*axes, "seq")
    return None, None, None


@dataclass
class Harness:
    """Everything the loop needs, built once from a config."""

    cfg: TrainConfig
    mesh: Any
    model: Any
    state: step_lib.TrainState
    train_step: Any
    eval_step: Any
    train_loader: ShardedLoader
    eval_loader: ShardedLoader
    manager: ckpt_lib.CheckpointManager | None
    start_step: int
    # (policy name, resolution source) from tpuframe.mem.resolve —
    # ("none", "default") when nothing elected a remat policy.
    remat_policy: tuple = ("none", "default")
    # (mode, resolution source) from tpuframe.parallel.zero1.resolve —
    # ("replicated", "default") when nothing elected weight-update sharding.
    weight_update: tuple = ("replicated", "default")
    # (mode, resolution source) from tpuframe.parallel.hier.resolve —
    # ("flat", "default") when nothing elected two-level collectives.
    hier: tuple = ("flat", "default")
    # (bucket threshold bytes, resolution source) from
    # tpuframe.parallel.fusion.resolve — (None, "default") when nothing
    # elected bucketed gradient fusion (per-leaf collectives).
    fusion_threshold: tuple = (None, "default")
    # (canonical spec string, resolution source) from
    # tpuframe.parallel.pspec.resolve — (None, "default") when the mesh
    # came from the config rather than a TPUFRAME_SPEC declaration.
    pspec: tuple = (None, "default")
    # Full provenance of an elastic n→n′ resize detected at build time
    # (committed checkpoint world ≠ current world), or None.  Emitted as
    # the typed ``elastic_resize`` run event.
    elastic_resize: dict | None = None


class _HarnessStep:
    """A train step whose first call traces it once and counts on that
    trace the flash forwards the block remat keeps (``mem.count_kept``;
    the call reuses the trace), and that hands each new ``model_state``
    to ``publish`` where the model has the hook (a reference, never a
    transfer); everything else is the step's own.  Each call is a
    ``train.dispatch`` span, and the device's run of the step a
    ``device.step`` interval (``timeline.device``, watched on a leaf of
    the step's metrics, which no later call donates)."""

    def __init__(self, step, publish=None):
        self._step, self._publish = step, publish
        self._counted = False
        self._calls = 0

    def __call__(self, state, batch):
        if not self._counted:
            self._counted = True
            try:
                mem.count_kept(self._step, state, batch)
            except Exception:  # noqa: BLE001 — a count, never a failed step
                pass
        n, self._calls = self._calls, self._calls + 1
        with span("train.dispatch", step=n):
            state, metrics = self._step(state, batch)
        leaf = next((x for x in jax.tree.leaves(metrics)
                     if hasattr(x, "block_until_ready")), None)
        if leaf is not None:   # launched when the dispatch span closed
            timeline_lib.device("step", timeline_lib.last(
                "train.dispatch").t1, output=leaf, step=n)
        if self._publish is not None:
            self._publish(state.model_state)
        return state, metrics

    def __getattr__(self, name):
        return getattr(self._step, name)


def _resolved_fusion(cfg: TrainConfig) -> tuple:
    """The step program's gradient-fusion bucket threshold with its
    provenance: TPUFRAME_FUSION_THRESHOLD env > the tuning DB's
    generation-gated ``fusion_threshold`` sweep winner > None
    (per-leaf).  One shared resolution for :func:`build_harness` and
    :func:`_lm_reduce_axis`, so the explicit-fusion step mode and its
    local-loss requirement cannot disagree about whether fusion is on."""
    from tpuframe.parallel import fusion as fusion_lib

    model_tag = cfg.model.replace("-", "_")
    return fusion_lib.resolve(
        program=f"train_{model_tag}_b{cfg.global_batch}",
        family="fusion_threshold")


def build_harness(cfg: TrainConfig) -> Harness:
    bootstrap.initialize()
    # Declarative parallelism spec: a TPUFRAME_SPEC declaration
    # ("dp=4,fsdp=2;slices=2") wins over the config's mesh — one string
    # names the whole hierarchical ICI×DCN layout, and the MeshSpec it
    # lowers to flows through every seam below (world resolution,
    # sharded-state detection, batch axes) unchanged.
    from tpuframe.parallel import pspec as pspec_lib

    spec, spec_source = pspec_lib.resolve()
    if spec is None:
        # Planner fallback: a `tune plan` winner (tune_db.json, family
        # plan_spec) supplies the spec when neither an argument nor
        # TPUFRAME_SPEC declared one — env > DB > default, the same
        # precedence every other tuned knob resolves under.  Gated on a
        # known target generation, so plain CPU test runs stay on the
        # config's mesh.
        from tpuframe.tune import db as tune_db

        planned = tune_db.resolve_spec("train_lm_tiny")
        if planned is not None:
            try:
                spec, spec_source = pspec_lib.parse_spec(planned), "plan"
            except pspec_lib.SpecError as e:
                raise pspec_lib.SpecError(
                    f"tune_db.json plan_spec winner {planned!r} does not "
                    f"parse: {e} — re-run `python -m tpuframe.tune plan` "
                    f"or set TPUFRAME_SPEC to override") from e
    if spec is not None:
        cfg = cfg.with_overrides(mesh=spec.mesh_spec())
        if bootstrap.is_primary():
            print(f"[tpuframe] parallelism spec '{spec.canonical()}' "
                  f"({spec_source}) -> mesh {cfg.mesh}", flush=True)
    # World resolution goes through the elastic resolver — the single
    # source of truth train.py and bench.py share, read at call time so a
    # relaunch at a new world size can never see a stale capture.
    from tpuframe import elastic as elastic_lib

    world = elastic_lib.current_world(cfg.mesh, distributed=cfg.distributed)
    mesh = world.mesh
    # Elastic resize detection: resuming onto a different world size than
    # the latest committed checkpoint was written at.  The declared
    # policy (TPUFRAME_ELASTIC_RESCALE: hold/linear/sqrt) rescales global
    # batch + LR HERE, before loaders and optimizer are built, so the
    # whole harness sees the post-resize config; restore then reshards
    # the ZeRO-1 state n→n′ from shapes alone (ckpt/checkpoint.py).
    elastic_resize = None
    if cfg.ckpt_dir is not None and cfg.resume:
        prev = ckpt_lib.committed_world(cfg.ckpt_dir)
        if prev and int(prev.get("devices", 0)) not in (0, world.n_devices):
            n_from = int(prev["devices"])
            policy, policy_src = elastic_lib.resolve_rescale()
            new_batch, new_lr = elastic_lib.rescale(
                cfg.global_batch, cfg.base_lr, n_from, world.n_devices,
                policy)
            elastic_resize = {
                "n_from": n_from,
                "n_to": world.n_devices,
                "processes_from": int(prev.get("processes", 0)) or None,
                "at_step": int(prev.get("step", 0)),
                "policy": policy,
                "policy_source": policy_src,
                "global_batch_from": cfg.global_batch,
                "global_batch_to": new_batch,
                "base_lr_from": cfg.base_lr,
                "base_lr_to": new_lr,
            }
            if (new_batch, new_lr) != (cfg.global_batch, cfg.base_lr):
                cfg = cfg.with_overrides(global_batch=new_batch,
                                         base_lr=new_lr)
            if bootstrap.is_primary():
                print(f"[tpuframe] elastic resize: {n_from}→"
                      f"{world.n_devices} devices at committed step "
                      f"{elastic_resize['at_step']} (policy={policy}, "
                      f"batch {elastic_resize['global_batch_from']}→"
                      f"{new_batch}, lr "
                      f"{elastic_resize['base_lr_from']:g}→{new_lr:g})",
                      flush=True)
    # Sharded-state (auto-SPMD) mode: ZeRO/FSDP over the fsdp axis and/or
    # Megatron-style TP over the model axis — both are placement decisions.
    use_sharded_state = mesh is not None and (
        mesh.shape["fsdp"] > 1 or mesh.shape["model"] > 1
        or mesh.shape["expert"] > 1)

    dtype = jnp.dtype(cfg.compute_dtype)
    model = models.get_model(cfg.model, dtype=dtype, **cfg.model_kwargs)

    train_ds, eval_ds = build_datasets(cfg)
    # Labels out of the head's range don't crash — one_hot silently yields
    # all-zero rows, training "runs" with a nonsense loss and eval goes
    # NaN.  Catch the config error (e.g. num_classes=10 on the 1000-class
    # synthetic imagenet) at build time with a message instead.
    n_cls = cfg.model_kwargs.get("num_classes")
    if (n_cls is not None and n_cls > 1 and not _is_lm_task(cfg)):
        for split_name, ds in (("train", train_ds), ("eval", eval_ds)):
            labels = ds.columns.get("label")
            if labels is not None and np.issubdtype(labels.dtype,
                                                    np.integer) and len(labels):
                hi = int(labels.max())
                if hi >= n_cls:
                    raise ValueError(
                        f"{split_name} labels reach {hi} but the model head "
                        f"has num_classes={n_cls} — label range and head "
                        f"size must match (check model_kwargs/dataset)")
    loader_part, step_part, reduce_axes = _batch_layout(cfg)
    # Float inputs are host-cast to the compute dtype before transfer (the
    # model's first op would cast them on device anyway; bf16 halves
    # infeed bytes — same rounding, same losses).
    cast = dtype if dtype != jnp.float32 else None
    train_loader = ShardedLoader(train_ds, cfg.global_batch, mesh,
                                 seed=cfg.seed, partition=loader_part,
                                 cast_floats=cast)
    eval_loader = ShardedLoader(eval_ds, cfg.global_batch, mesh,
                                shuffle=False, partition=loader_part,
                                cast_floats=cast)

    sample = train_ds[:2]
    rng = jax.random.key(cfg.seed)
    # One program, not an op at a time: a block with a few hundred ops
    # (afmoe's routing) costs minutes of per-op dispatch otherwise.
    init = jax.jit(model.init)
    if _is_lm_task(cfg):
        # a decoder's parameters do not depend on the sequence's length:
        # 128 tokens make the same tree from a far smaller program
        variables = init(rng, jnp.asarray(sample["input_ids"][:, :128]))
    elif _is_text_task(cfg):
        variables = init(rng, jnp.asarray(sample["input_ids"]))
    else:
        variables = init(
            rng, _maybe_normalize(cfg, jnp.asarray(sample["image"])))
    params = variables["params"]
    model_state = {k: v for k, v in variables.items() if k != "params"}

    use_pp = mesh is not None and mesh.shape["pipe"] > 1
    if use_pp and cfg.grad_clip_norm is not None:
        # optax's clip computes the norm from local leaf values — a
        # per-STAGE statistic under the pipe-sharded layout; build the
        # chain with the vma-aware cross-stage clip instead (once — pp
        # models sit near the memory limit, no throwaway Adam trees).
        import optax

        from tpuframe.parallel.pp_lm import pp_clip_by_global_norm

        tx = optax.chain(
            pp_clip_by_global_norm(cfg.grad_clip_norm),
            build_optimizer(cfg.with_overrides(grad_clip_norm=None),
                            params))
    else:
        tx = build_optimizer(cfg, params)
    state = step_lib.TrainState.create(params, tx, model_state=model_state,
                                       rng=jax.random.key(cfg.seed + 1))

    # Rematerialization policy: TPUFRAME_REMAT_POLICY env (or the legacy
    # TPUFRAME_BENCH_REMAT alias) wins, else the tuning DB's offline remat
    # sweep winner (generation-gated, like the XLA opts above), else none.
    model_tag = cfg.model.replace("-", "_")
    remat_policy, remat_source = mem.resolve(
        program=f"train_{model_tag}_b{cfg.global_batch}",
        family=f"remat_{model_tag}")
    step_policy = None if remat_policy == "none" else remat_policy

    # Weight-update sharding (ZeRO-1): TPUFRAME_WEIGHT_UPDATE env wins,
    # else the tuning DB's offline weight_update_* sweep winner
    # (generation-gated), else replicated.  zero1 is the plain-DP
    # shard_map path only — on configs it cannot serve (pp, auto-SPMD
    # sharded state, no mesh, adasum) a DB-elected mode falls back
    # silently (a stale DB row must never break a run) while an explicit
    # env ask gets make_train_step's specific error.
    from tpuframe.parallel import zero1 as zero1_lib

    weight_update, wu_source = zero1_lib.resolve(
        program=f"train_{model_tag}_b{cfg.global_batch}",
        family=f"weight_update_{model_tag}")
    if (weight_update == "zero1" and wu_source != "env"
            and (use_pp or use_sharded_state or mesh is None
                 or cfg.grad_reduce == "adasum")):
        weight_update, wu_source = "replicated", "default"

    # Hierarchical two-level collectives: TPUFRAME_HIER env wins, else
    # the DB's offline hier_collectives sweep winner (generation-gated),
    # else flat.  Same fallback discipline: on configs the two-level
    # lowering cannot serve (pp, auto-SPMD sharded state, no mesh,
    # adasum, sequence sharding) a DB-elected mode demotes silently
    # while an explicit env ask gets make_train_step's specific error.
    from tpuframe.parallel import hier as hier_lib

    hier_mode, hier_source = hier_lib.resolve(
        program=f"train_{model_tag}_b{cfg.global_batch}",
        family=hier_lib.DB_FAMILY)
    if (hier_mode != "flat" and hier_source != "env"
            and (use_pp or use_sharded_state or mesh is None
                 or cfg.grad_reduce == "adasum" or cfg.shard_seq)):
        hier_mode, hier_source = "flat", "default"

    # GPipe pp takes no gradient-fusion modifier; the knob resolves (and
    # can be DB-elected) only on the shard_map branch below.
    fusion_threshold, ft_source = None, "default"

    if use_pp:
        # Pipeline parallelism: ScanBlockLM blocks + opt state sharded over
        # the pipe axis, GPipe microbatching (tpuframe.parallel.pp_lm).
        if cfg.model != "transformer-lm-pp":
            raise ValueError(
                f"mesh pipe={mesh.shape['pipe']} needs model="
                f"'transformer-lm-pp' (layer-stacked blocks); got "
                f"{cfg.model!r}")
        if use_sharded_state:
            raise ValueError("pipe parallelism does not compose with "
                             "fsdp/model/expert sharded-state axes yet")
        if cfg.accum_steps != 1:
            raise ValueError("pipe parallelism has its own microbatching "
                             "(pp_microbatches); accum_steps must be 1")
        if cfg.grad_reduce != "mean":
            raise ValueError("pipe parallelism supports grad_reduce='mean' "
                             "only (the pp step has its own cross-stage "
                             "reduction)")
        if cfg.shard_seq:
            raise ValueError("pipe parallelism does not compose with "
                             "shard_seq sequence parallelism yet")
        if weight_update == "zero1":
            raise ValueError("TPUFRAME_WEIGHT_UPDATE=zero1 is the plain-DP "
                             "shard_map path; the pipeline step owns its "
                             "own stage-sharded update")
        if hier_mode != "flat":
            raise ValueError("TPUFRAME_HIER=hier is the plain-DP "
                             "shard_map path; the pipeline step owns its "
                             "own cross-stage communication")
        from tpuframe.parallel import pp_lm

        factory, place_state, _ = pp_lm.make_pp_lm_step(
            model, tx, mesh, n_micro=cfg.pp_microbatches,
            fused_xent=cfg.fused_xent, remat_policy=step_policy)
        state = place_state(state)
        train_step = factory(state)
        eval_step = pp_lm.make_pp_lm_eval(
            model, mesh, n_micro=cfg.pp_microbatches,
            fused_xent=cfg.fused_xent)(state)
    else:
        state_shardings = None
        if use_sharded_state:
            from tpuframe.parallel import fsdp as fsdp_lib

            tp_rules = None
            if mesh.shape["model"] > 1 or mesh.shape["expert"] > 1:
                from tpuframe.parallel import tp as tp_lib

                tp_rules = tp_lib.rules_for_model(cfg.model)
            state_shardings = fsdp_lib.state_shardings(state, mesh,
                                                       tp_rules=tp_rules)
            state = jax.tree.map(mesh_lib.host_device_put, state,
                                 state_shardings)
        elif mesh is not None:
            if weight_update == "zero1":
                # Optimizer state born sharded in zero1's flat padded
                # layout — never materialized replicated on any chip.
                state = zero1_lib.make_state(
                    params, tx, mesh, model_state=model_state,
                    rng=jax.random.key(cfg.seed + 1))
            else:
                state = step_lib.replicate_state(state, mesh)

        loss_fn = make_loss_fn(cfg, model)
        from tpuframe.tune import db as tune_db
        from tpuframe.utils import xla_opts as xla_opts_lib

        # Per-compile compiler options: TPUFRAME_XLA_OPTS env wins, else
        # the offline tuning DB (tpuframe.tune; only engages when the
        # target TPU generation is known).  This is how queue-6's
        # scheduler-flag A/Bs run through the real training loop.
        xla_opts = xla_opts_lib.from_env()
        if xla_opts is None:
            xla_opts = tune_db.resolve_xla_opts(cfg.name,
                                                family="train_step")
        # Gradient-fusion bucket threshold: same resolution shape as the
        # other knobs — TPUFRAME_FUSION_THRESHOLD env wins, else the
        # DB's generation-gated fusion_threshold sweep winner, else
        # per-leaf.  A DB-elected threshold serves
        # the shard_map gradient path only: where the step ignores the
        # knob (unmapped jit, auto-SPMD sharded state) it demotes
        # silently.
        fusion_threshold, ft_source = _resolved_fusion(cfg)
        if (fusion_threshold is not None and ft_source != "env"
                and (mesh is None or use_sharded_state)):
            fusion_threshold, ft_source = None, "default"
        train_step = step_lib.make_train_step(
            loss_fn, tx, mesh, batch_partition=step_part,
            reduce_axes=reduce_axes, state_shardings=state_shardings,
            fusion_threshold=fusion_threshold,
            accum_steps=cfg.accum_steps,
            grad_reduce=cfg.grad_reduce,
            compiler_options=xla_opts,
            remat_policy=step_policy,
            weight_update=weight_update,
            hier=hier_mode)
        eval_step = step_lib.make_eval_step(
            make_metric_fn(cfg, model), mesh, batch_partition=step_part,
            reduce_axes=reduce_axes, state_shardings=state_shardings)
        train_step = _HarnessStep(train_step,
                                  getattr(model, "publish_state", None))

    manager = None
    start_step = 0
    if cfg.track_best and cfg.ckpt_dir is None:
        raise ValueError("track_best=True needs ckpt_dir (the best/ "
                         "checkpoint lives under it)")
    if cfg.ckpt_dir is not None:
        # TPUFRAME_ASYNC_CKPT overrides the config knob when set — the
        # ops-side switch for flipping a fleet to async saves (or back)
        # without touching run configs.
        async_env = os.environ.get("TPUFRAME_ASYNC_CKPT", "")
        ckpt_async = (async_env not in ("0", "false", "")
                      if async_env else cfg.ckpt_async)
        manager = ckpt_lib.CheckpointManager(
            cfg.ckpt_dir, every_steps=cfg.ckpt_every, keep=cfg.ckpt_keep,
            async_write=ckpt_async)
        if cfg.resume:
            resumed = manager.restore_latest(mesh=mesh, target=state)
            if resumed is not None:
                start_step, state = resumed
                if bootstrap.is_primary():
                    print(f"[tpuframe] resumed from step {start_step}",
                          flush=True)

    return Harness(cfg=cfg, mesh=mesh, model=model, state=state,
                   train_step=train_step, eval_step=eval_step,
                   train_loader=train_loader, eval_loader=eval_loader,
                   manager=manager, start_step=start_step,
                   remat_policy=(remat_policy, remat_source),
                   weight_update=(weight_update, wu_source),
                   hier=(hier_mode, hier_source),
                   fusion_threshold=(fusion_threshold, ft_source),
                   pspec=(spec.canonical() if spec is not None else None,
                          spec_source),
                   elastic_resize=elastic_resize)


def _lm_reduce_axis(cfg: TrainConfig, *, for_grad: bool):
    """Mesh axes for the GLOBAL valid-token mean (losses.masked_mean):
    per-shard masked means pmean-ed uniformly are biased when shards hold
    unequal valid counts (padded_docs).  The explicit-fusion and
    grad-accumulation step modes differentiate a LOCAL loss and reduce
    gradients themselves — a psum inside the loss would mis-scale them —
    so the gradient-side global mean only applies in the default implicit
    mode, and the biased combination is refused outright."""
    axes = ((*_cfg_batch_axes(cfg), "seq") if cfg.shard_seq
            else _cfg_batch_axes(cfg))
    if not for_grad:
        return axes  # eval metrics have no explicit-reduction mode
    # The local-loss requirement only exists where make_train_step actually
    # takes the explicit path: shard_map mode (distributed, no sharded-state
    # axes).  Unmapped jit and auto-SPMD ignore the fusion knob and reduce
    # globally by construction; a psum with unbound axes is a no-op there.
    sharded_state = (cfg.mesh.fsdp > 1 or cfg.mesh.model > 1
                     or cfg.mesh.expert > 1)
    shard_map_mode = cfg.distributed and not sharded_state
    explicit = shard_map_mode and (_resolved_fusion(cfg)[0] is not None
                                   or cfg.accum_steps > 1
                                   or cfg.grad_reduce == "adasum")
    if not explicit:
        return axes
    if bool(cfg.dataset_kwargs.get("padded_docs")):
        raise ValueError(
            "padded_docs with TPUFRAME_FUSION_THRESHOLD, accum_steps>1 or "
            "grad_reduce='adasum' in shard_map mode: these paths need a "
            "local loss, and a per-shard valid-token mean would be biased "
            "by unequal padding across shards")
    return None  # local loss; no -100 labels, so per-shard mean is exact


def make_loss_fn(cfg: TrainConfig, model) -> step_lib.LossFn:
    if _is_lm_task(cfg):
        aux_w = float(cfg.model_kwargs.get("moe_aux_weight", 0.01))
        raxis = _lm_reduce_axis(cfg, for_grad=True)

        def loss_fn(params, model_state, batch, rng):
            # what the model keeps between steps (afmoe's routing counters)
            # is mutable beside the auxiliary losses it sows
            kept = [k for k in model_state if k != "aux_loss"]
            if cfg.fused_xent:
                # Chunked fused head+loss: [B,S,V] logits never hit HBM
                # (tpuframe.ops.fused_xent); the argmax for token accuracy
                # rides in the same vocab sweep.
                from tpuframe.ops import fused_xent as fx

                hidden, sown = model.apply(
                    {"params": params, **model_state}, batch["input_ids"],
                    train=True, rngs={"dropout": rng},
                    mutable=["aux_loss", *kept], hidden_only=True)
                loss, acc = fx.mean_xent_and_accuracy(
                    hidden, params["lm_head"]["kernel"], batch["labels"],
                    ignore_index=-100, reduce_axis=raxis)
                metrics = {"accuracy": acc}
            else:
                logits, sown = model.apply({"params": params, **model_state},
                                           batch["input_ids"], train=True,
                                           rngs={"dropout": rng},
                                           mutable=["aux_loss", *kept])
                # ignore_index=-100: the torch/HF convention — padded
                # label positions (datasets.lm_text padded_docs) carry -100
                # and contribute neither loss nor gradient; a no-op for
                # packed streams with no negative labels.
                loss = losses.softmax_cross_entropy(logits, batch["labels"],
                                                    ignore_index=-100,
                                                    reduce_axis=raxis)
                metrics = {"accuracy": losses.accuracy(logits,
                                                       batch["labels"],
                                                       ignore_index=-100,
                                                       reduce_axis=raxis)}
            if kept:
                model_state = {**model_state, **{k: sown[k] for k in kept}}
            aux_leaves = jax.tree.leaves(sown.get("aux_loss", {}))
            if aux_leaves:  # MoE load-balance penalty (tpuframe.ops.moe)
                aux = sum(aux_leaves) / len(aux_leaves)
                loss = loss + aux_w * aux
                metrics["moe_aux"] = aux
            return loss, (model_state, metrics)

        return loss_fn

    if _is_text_task(cfg):
        regression = _is_regression_task(cfg)

        def loss_fn(params, model_state, batch, rng):
            logits = model.apply(
                {"params": params, **model_state}, batch["input_ids"],
                batch["attention_mask"], batch["token_type_ids"], train=True,
                rngs={"dropout": rng})
            if regression:
                pred = logits[..., 0]
                loss = jnp.mean((pred - batch["label"]) ** 2)
                return loss, (model_state, {"mse": loss})
            loss = losses.softmax_cross_entropy(logits, batch["label"])
            return loss, (model_state,
                          {"accuracy": losses.accuracy(logits, batch["label"])})

        return loss_fn

    def loss_fn(params, model_state, batch, rng):
        images = batch["image"]
        if cfg.augment != "none":
            from tpuframe.data import augment as augment_lib

            aug_rng, rng = jax.random.split(rng)
            images = augment_lib.apply(cfg.augment, images, aug_rng,
                                       crop=cfg.augment_crop)
        outputs = model.apply(
            {"params": params, **model_state},
            _maybe_normalize(cfg, images), train=True,
            rngs={"dropout": rng},
            mutable=list(model_state) if model_state else False)
        if model_state:
            logits, mutated = outputs
            model_state = dict(mutated)
        else:
            logits = outputs
        loss = losses.softmax_cross_entropy(logits, batch["label"],
                                            cfg.label_smoothing)
        return loss, (model_state,
                      {"accuracy": losses.accuracy(logits, batch["label"])})

    return loss_fn


def make_metric_fn(cfg: TrainConfig, model):
    if _is_lm_task(cfg):
        if cfg.fused_xent:
            # Eval must honor the fused path too: lm_long's eval logits
            # would be ~4 GB f32 per 32k-token sequence otherwise.
            from tpuframe.ops import fused_xent as fx

            raxis = _lm_reduce_axis(cfg, for_grad=False)

            def metric_fn(params, model_state, batch):
                hidden = model.apply({"params": params, **model_state},
                                     batch["input_ids"], hidden_only=True)
                loss, acc = fx.mean_xent_and_accuracy(
                    hidden, params["lm_head"]["kernel"], batch["labels"],
                    ignore_index=-100, reduce_axis=raxis)
                return {"loss": loss, "perplexity": jnp.exp(loss),
                        "accuracy": acc}

            return metric_fn

        raxis = _lm_reduce_axis(cfg, for_grad=False)

        def metric_fn(params, model_state, batch):
            logits = model.apply({"params": params, **model_state},
                                 batch["input_ids"])
            loss = losses.softmax_cross_entropy(logits, batch["labels"],
                                                ignore_index=-100,
                                                reduce_axis=raxis)
            return {"loss": loss, "perplexity": jnp.exp(loss),
                    "accuracy": losses.accuracy(logits, batch["labels"],
                                                ignore_index=-100,
                                                reduce_axis=raxis)}

        return metric_fn

    if _is_text_task(cfg):
        regression = _is_regression_task(cfg)

        def metric_fn(params, model_state, batch):
            logits = model.apply({"params": params, **model_state},
                                 batch["input_ids"], batch["attention_mask"],
                                 batch["token_type_ids"])
            if regression:
                pred = logits[..., 0]
                y = batch["label"]
                mse = jnp.mean((pred - y) ** 2)
                # First/second moments as per-batch MEANS: evaluate()'s
                # averaging over equal-size batches then reproduces the
                # whole-set moments exactly, from which _finalize_eval
                # derives the task's standard Pearson r without a second
                # pass or per-example host traffic.
                return {"loss": mse, "mse": mse,
                        "_m_pred": jnp.mean(pred), "_m_y": jnp.mean(y),
                        "_m_pred2": jnp.mean(pred ** 2),
                        "_m_y2": jnp.mean(y ** 2),
                        "_m_py": jnp.mean(pred * y)}
            out = {"accuracy": losses.accuracy(logits, batch["label"]),
                   "loss": losses.softmax_cross_entropy(logits,
                                                        batch["label"])}
            if cfg.dataset == "glue_cola":
                # Confusion-rate moments: equal-size eval batches mean
                # evaluate()'s averaging reproduces whole-set rates, from
                # which _finalize_eval derives the task's standard
                # Matthews correlation (scale cancels in MCC).
                pred = jnp.argmax(logits, -1)
                y = batch["label"]
                out.update(
                    _m_tp=jnp.mean((pred == 1) & (y == 1)),
                    _m_fp=jnp.mean((pred == 1) & (y == 0)),
                    _m_tn=jnp.mean((pred == 0) & (y == 0)),
                    _m_fn=jnp.mean((pred == 0) & (y == 1)))
            return out

        return metric_fn

    def metric_fn(params, model_state, batch):
        images = batch["image"]
        if cfg.augment == "crop_flip" and cfg.augment_crop:
            from tpuframe.data import augment as augment_lib

            # train random-crops from larger stored images; eval pairs it
            # with the deterministic center crop at the same geometry.
            images = augment_lib.center_crop(images, cfg.augment_crop)
        logits = model.apply({"params": params, **model_state},
                             _maybe_normalize(cfg, images))
        out = {"accuracy": losses.accuracy(logits, batch["label"]),
               "loss": losses.softmax_cross_entropy(logits, batch["label"])}
        if batch["label"].shape and cfg.dataset == "imagenet":
            out["top5"] = losses.topk_accuracy(logits, batch["label"], 5)
        return out

    return metric_fn


def evaluate(h: Harness, max_batches: int) -> dict:
    # Accumulate on device: per-batch metric dicts are summed as device
    # arrays (async dispatch, no host sync), and the ONE device_get at the
    # end fetches the whole pass — the reference's eval loop does one small
    # allreduce per metric per batch and a host read each time (SURVEY.md
    # §4.5); here host↔device traffic is a single transfer per eval.
    agg: dict | None = None
    n = 0
    for i, batch in enumerate(h.eval_loader.epoch(0)):
        if i >= max_batches:
            break
        m = h.eval_step(h.state, batch)
        agg = m if agg is None else jax.tree.map(jnp.add, agg, m)
        n += 1
        if n % 8 == 0:
            # Bound device-memory run-ahead: without a sync the loader can
            # device_put batches faster than eval consumes them and in-flight
            # buffers pile up in HBM.  block_until_ready is a sync, not a
            # transfer — the one-device_get-per-eval contract holds.
            jax.block_until_ready(agg)
    if agg is None:
        return {}
    return _finalize_eval({k: float(v) / n
                           for k, v in jax.device_get(agg).items()})


def _finalize_eval(avg: dict) -> dict:
    """Derive set-level metrics from aggregated moments (keys starting
    with ``_m_``), which are internal and dropped from the report."""
    if "_m_tp" in avg:
        tp, fp = avg["_m_tp"], avg["_m_fp"]
        tn, fn = avg["_m_tn"], avg["_m_fn"]
        denom = ((tp + fp) * (tp + fn) * (tn + fp) * (tn + fn)) ** 0.5
        if denom > 0:
            avg["mcc"] = (tp * tn - fp * fn) / denom
    if "_m_py" in avg:
        var_p = avg["_m_pred2"] - avg["_m_pred"] ** 2
        var_y = avg["_m_y2"] - avg["_m_y"] ** 2
        cov = avg["_m_py"] - avg["_m_pred"] * avg["_m_y"]
        if var_p > 0 and var_y > 0:
            avg["pearson"] = cov / (var_p * var_y) ** 0.5
    return {k: v for k, v in avg.items() if not k.startswith("_m_")}


def _tune_db_fingerprint() -> str | None:
    """sha256 prefix of the tuning-DB file feeding this run's XLA opts
    (None when no DB exists) — the run_start manifest field that ties a
    run record to the exact tuned-flag state it trained under."""
    try:
        from tpuframe.tune import db as tune_db

        with open(tune_db.default_db_path(), "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()[:16]
    except Exception:  # noqa: BLE001 — no DB / unreadable: not a run error
        return None


def _step_costs(train_step, state, batch):
    """Whole-program (flops, bytes accessed) of one train step from the
    *lowered* module's cost analysis — tracing only, no compile
    (Lowered.cost_analysis works pre-compile on this jax).  Returns
    (flops, bytes, "cost_analysis") or (None, None, None) when the path is
    unavailable (pp factory steps, older jax) — callers fall back to the
    analytic 6·N·D flops estimate (bytes has no analytic fallback: the
    HBM-utilization row simply doesn't print without a cost model)."""
    try:
        ca = train_step.lower(state, batch).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0.0) or 0.0)
        nbytes = float(ca.get("bytes accessed", 0.0) or 0.0)
        if flops > 0:
            return flops, (nbytes if nbytes > 0 else None), "cost_analysis"
    except Exception:  # noqa: BLE001 — cost model optional by design
        pass
    return None, None, None


def train(cfg: TrainConfig, *, trace_dir: str | None = None,
          log_file: str | None = None) -> dict:
    """Run the workload; returns final metrics (the driver/test surface).

    Thin shell around the real loop: any escaping exception first dumps
    the flight recorder's ring (``obs/flight.py``) so the postmortem has
    the last-N events even when the JSONL log's tail was torn.  However
    the loop ends, the threads it started (prefetch workers, heartbeat,
    device-memory sampler) are stopped and joined before this returns: a
    daemon thread still inside a jax call when the interpreter finalizes
    aborts the process after the run has succeeded."""
    with contextlib.ExitStack() as threads:
        try:
            return _train_impl(cfg, threads, trace_dir=trace_dir,
                               log_file=log_file)
        except SystemExit:
            raise  # clean exits (preemption rc 14) are not crashes
        except BaseException:
            flight_lib.dump("exception")
            raise


def _train_impl(cfg: TrainConfig, threads: contextlib.ExitStack, *,
                trace_dir: str | None = None,
                log_file: str | None = None) -> dict:
    # Preemption contract (resilience/preempt.py): installed before the
    # harness so a SIGTERM during compile/restore is already caught; the
    # loop below checkpoints at the next step boundary and exits rc 14.
    guard = PreemptionGuard().install()
    # Structured run-event log (obs/events.py): env-gated — opened before
    # build_harness so restore-time ckpt_restore events land in the file.
    # The goodput meter starts here too: everything before the first step
    # (harness build, data, restore, compile-cache setup) is "init".
    events_lib.init()
    # Flight recorder tees every emitted record into a bounded ring so a
    # crash/preemption/stall dump carries the last-N events even when the
    # JSONL tail was torn (installed right after init so the ring sees
    # restore-time events too).
    flight_lib.install()
    meter = goodput_lib.GoodputMeter()
    # On-demand profiling endpoint (TensorBoard "capture profile"): env-
    # gated, best-effort — a busy port must not kill training.
    profiler_port = os.environ.get("TPUFRAME_PROFILER_PORT", "").strip()
    if profiler_port:
        try:
            start_profiler_server(int(profiler_port))
        except ValueError:
            pass
    # Persistent compilation cache (utils/compile_cache): a relaunch or
    # crash-loop restart of the same program compiles from the on-disk
    # cache instead of from scratch — hit/miss counters land in the final
    # metrics below next to the retry.* counters.
    from tpuframe.utils import compile_cache

    compile_cache.enable()
    # Re-parse TPUFRAME_FAULTS per run: in-process callers (tests) invoke
    # train() repeatedly under different envs, and restore-time gcs reads
    # inside build_harness already pass through the seams.
    faults_lib.reset_from_env()
    # Each run reports its own Pallas kernel resolutions, once.
    from tpuframe.ops import kernel_impl

    kernel_impl.reset()
    h = build_harness(cfg)
    threads.callback(h.train_loader.close)
    threads.callback(h.eval_loader.close)
    threads.callback(timeline_lib.stop_watcher)
    # An elastic resize may have rescaled global_batch/base_lr inside
    # build_harness — everything below reads the config the harness was
    # actually built with.
    cfg = h.cfg
    # In distributed mode build_harness ran jax.distributed.initialize,
    # whose preemption notifier steals SIGTERM (it only logs the signal);
    # take it back so rc-14 preemption works under the supervisor too.
    guard.reassert()
    logger = MetricLogger(
        log_file, tb_dir=cfg.tb_dir or os.environ.get("TPUFRAME_TB_DIR"))
    rate = RateMeter()
    timeline = StepTimeline.from_env()  # HOROVOD_TIMELINE parity (§5.1)

    # Collective-timeout surfacing (SURVEY.md §5.3): a hung step — peer host
    # dead mid-collective, wedged infeed, dead coordinator — becomes a clean
    # nonzero exit instead of an indefinite hang, so the slice launcher can
    # restart the job and it auto-resumes from the last committed checkpoint.
    # The watchdog arms after the first completed step (compile is unbounded).
    stall_timeout = float(os.environ.get("TPUFRAME_STALL_TIMEOUT_S", "300"))
    stall_poll = float(os.environ.get("TPUFRAME_STALL_POLL_S", "5"))
    stall_abort = os.environ.get("TPUFRAME_STALL_ABORT", "1") == "1"

    # Mutable run facts the event-emitting closures need (filled in once
    # the harness/flops model is known; read from the watchdog thread).
    run_info: dict = {"flops": None, "flops_source": None, "bytes": None,
                      "generation": None, "generation_source": None,
                      "devmem": None, "step": h.start_step}

    def _emit_run_end(final_step: int) -> None:
        """Close the books: goodput buckets, both MFU flavors, peak HBM
        and the full counter table, in one run_end record."""
        if not events_lib.enabled():
            return
        summary = meter.summary()
        extra: dict = {}
        flops = run_info["flops"]
        prod_steps = summary["productive_steps"]
        prod_s = summary["buckets"]["productive"]
        if flops and prod_steps and prod_s > 0:
            extra["mfu_productive"] = round(goodput_lib.mfu(
                flops, prod_s / prod_steps,
                generation=run_info["generation"],
                n_devices=jax.device_count()), 6)
            if summary["wall_s"] > 0:
                extra["mfu_goodput"] = round(goodput_lib.mfu(
                    flops * prod_steps, summary["wall_s"],
                    generation=run_info["generation"],
                    n_devices=jax.device_count()), 6)
        if run_info["bytes"] and prod_steps and prod_s > 0:
            extra["hbm_util_productive"] = round(goodput_lib.hbm_util(
                run_info["bytes"], prod_s / prod_steps,
                generation=run_info["generation"],
                n_devices=jax.device_count()), 6)
        if run_info["devmem"] is not None:
            extra.update(run_info["devmem"].peak_summary())
        events_lib.emit("run_end", final_step=final_step,
                        wall_s=summary["wall_s"], goodput=summary,
                        generation=run_info["generation"],
                        generation_source=run_info["generation_source"],
                        counters=obs_metrics.counters(), **extra)

    def _on_stall(idle: float) -> None:
        if not stall_abort:
            return
        import sys

        print(f"[tpuframe] STALL: no step completed in {idle:.0f}s — "
              f"aborting for clean restart + checkpoint resume (exit 13)",
              file=sys.stderr, flush=True)
        try:
            # The heartbeat already emitted the structured stall event;
            # here the dying attempt commits its own books so summarize
            # works from the recorded run_end instead of reconstructing.
            # Capped at the unattributed remainder: the idle window can
            # overlap a step that completed without beating, and the
            # buckets must never sum past wall.
            meter.charge("stall", min(idle, meter.unaccounted_s()))
            _emit_run_end(run_info["step"])
            flight_lib.dump("stall_abort")
            events_lib.close()
            logger.close()
            if timeline is not None:
                timeline.instant("stall_abort", idle_s=idle)
                timeline.close()
            exporter_lib.stop()  # final textfile flush rides on stop()
        finally:
            os._exit(13)

    heartbeat = Heartbeat(timeout_s=stall_timeout, poll_s=stall_poll,
                          on_stall=_on_stall,
                          arm_after_first_beat=True).start()
    threads.callback(heartbeat.stop)

    # Live telemetry plane (obs/exporter.py): /metrics + /healthz, env-
    # gated.  The health probe is the heartbeat watchdog — a run that
    # stops completing steps reads 503 before the stall-abort kills it.
    exporter = exporter_lib.start_from_env(
        health=lambda: not heartbeat.stalled)
    if exporter is not None:
        def _goodput_samples():
            s = meter.summary()
            out = [("tpuframe_goodput_bucket_seconds", {"bucket": k}, v)
                   for k, v in s["buckets"].items()]
            out.append(("tpuframe_wall_seconds", {}, s["wall_s"]))
            out.append(("tpuframe_steps_completed", {}, s["steps"]))
            return out

        def _devmem_samples():
            sampler = run_info["devmem"]
            if sampler is None:
                return []
            peaks = sampler.peak_summary()
            out = []
            if peaks.get("peak_hbm_bytes") is not None:
                out.append(("tpuframe_hbm_peak_bytes", {},
                            peaks["peak_hbm_bytes"]))
            for did, b in (peaks.get("per_device") or {}).items():
                out.append(("tpuframe_hbm_device_peak_bytes",
                            {"device": did}, b))
            return out

        exporter.add_collector(_goodput_samples)
        exporter.add_collector(_devmem_samples)
    examples_per_step = cfg.global_batch

    if bootstrap.is_primary():
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(h.state.params))
        print(f"[tpuframe] {cfg.name}: model={cfg.model} "
              f"params={n_params/1e6:.2f}M devices={jax.device_count()} "
              f"global_batch={cfg.global_batch} steps={cfg.total_steps}",
              flush=True)

    # Structured fault injection (resilience/faults.py): TPUFRAME_FAULTS
    # arms named seams (the removed TPUFRAME_FAULT_STEP/_ONCE aliases
    # raise at registry build with the spelling to use).  once=1 faults
    # are dropped on a resumed run so relaunch/resume tests survive the
    # step that killed them.  HANG_STEP/HANG_RANK stay env-level: the
    # rank gate below needs jax.process_index().
    faults_lib.set_resumed(h.start_step > 0)
    hang_step = int(os.environ.get("TPUFRAME_HANG_STEP", "0") or "0")
    hang_rank = int(os.environ.get("TPUFRAME_HANG_RANK", "-1") or "-1")
    if hang_rank >= 0 and jax.process_index() != hang_rank:
        hang_step = 0

    state = h.state
    step = h.start_step
    final_train_metrics: dict = {}
    data_iter: Iterator = h.train_loader.from_step(step)

    if os.environ.get("TPUFRAME_CHECK_SPMD") == "1":
        # Debug mode (SURVEY.md §5.2): every host verifies it built the same
        # config AND the same lowered step program before any collective runs
        # — the host-dependent-trace divergence class.
        from tpuframe.obs import spmd_check

        spmd_check.assert_uniform_across_hosts("config", repr(cfg))
        if step < cfg.total_steps:
            first = next(data_iter)
            spmd_check.check_step_program(h.train_step, "train_step",
                                          state, first)
            data_iter = itertools.chain([first], data_iter)

    if events_lib.enabled():
        # Run manifest + flops model.  The flops count comes from tracing
        # the step once (no compile); the analytic 6·N·D estimate is the
        # fallback — either way run_start records a nonzero flops_per_step
        # so MFU is recomputable offline even from a crashed log.
        from tpuframe.tune import roofline

        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(h.state.params))
        # The chip the MFU/HBM rows are priced against, and where that
        # came from (device / env / assumed — a CPU run is `assumed`).
        run_info["generation"], run_info["generation_source"] = \
            roofline.device_generation()
        if step < cfg.total_steps:
            first = next(data_iter)
            flops, nbytes, src = _step_costs(h.train_step, state, first)
            data_iter = itertools.chain([first], data_iter)
        else:
            flops, nbytes, src = None, None, None
        if not flops:
            flops = goodput_lib.flops_fallback(n_params, examples_per_step)
            src = "analytic_6nd"
        run_info["flops"], run_info["flops_source"] = flops, src
        run_info["bytes"] = nbytes
        events_lib.emit(
            "run_start", config=cfg.name,
            config_hash=hashlib.sha256(repr(cfg).encode()).hexdigest()[:16],
            jax_version=jax.__version__,
            devices=jax.device_count(), processes=jax.process_count(),
            mesh=dict(h.mesh.shape) if h.mesh is not None else None,
            tune_db=_tune_db_fingerprint(),
            xla_opts=os.environ.get("TPUFRAME_XLA_OPTS") or None,
            start_step=h.start_step, total_steps=cfg.total_steps,
            global_batch=cfg.global_batch, n_params=n_params,
            generation=run_info["generation"],
            generation_source=run_info["generation_source"],
            device_kind=jax.devices()[0].device_kind,
            flops_per_step=flops, flops_source=src,
            bytes_per_step=nbytes)
        # The chosen remat policy as its own typed record: joinable with
        # the tuning DB (same policy names) and visible in summarize even
        # when the run dies before run_end.
        events_lib.emit("remat_policy", policy=h.remat_policy[0],
                        source=h.remat_policy[1],
                        predicted_bytes_per_step=nbytes)
        # Weight-update sharding provenance, same contract: which mode the
        # run actually compiled with and who elected it (env / tune_db /
        # default) — the analyzer joins this with devmem's HBM samples to
        # attribute optimizer-state residency deltas.
        from tpuframe.parallel import zero1 as zero1_lib

        events_lib.emit(
            "weight_update", mode=h.weight_update[0],
            source=h.weight_update[1],
            n_shards=(zero1_lib.world_size(h.mesh)
                      if h.mesh is not None else 1))
        # Two-level-collective provenance, same contract: whether the
        # lowering that keeps full gradient bytes on ICI was compiled in
        # and who elected it.
        events_lib.emit("hier", mode=h.hier[0], source=h.hier[1])
        # Gradient-fusion provenance, same contract: which bucket
        # threshold the step actually compiled with (None = per-leaf)
        # and who elected it — the analyzer joins this with the
        # schedule plane's interior-window records to attribute
        # overlap-score deltas to the knob that moved them.
        events_lib.emit("fusion_threshold", threshold=h.fusion_threshold[0],
                        source=h.fusion_threshold[1])
        # Parallelism-spec provenance: which declarative spec (if any)
        # the run's mesh was lowered from and who elected it — joins
        # the run manifest's mesh dict to the TPUFRAME_SPEC grammar, so
        # the analyzer can tie ICI/DCN comm attribution back to the
        # declared hierarchical layout.
        if h.pspec[0] is not None:
            events_lib.emit("pspec", spec=h.pspec[0], source=h.pspec[1])
        # Elastic resize provenance: the world changed across the attempt
        # boundary.  n_from/n_to, the declared rescale policy and the
        # exact batch/LR transition, as one typed record — the obs
        # stitcher joins this with the per-attempt step high-water marks
        # to prove the ≤1-lost-step invariant across the resize.
        if h.elastic_resize is not None:
            events_lib.emit("elastic_resize", **h.elastic_resize)
        run_info["devmem"] = devmem_lib.DevmemSampler(
            interval_s=float(os.environ.get("TPUFRAME_DEVMEM_INTERVAL_S",
                                            "30"))).start()
        threads.callback(run_info["devmem"].stop)
        meter.charge("init", meter.wall_s())
    # Profiler trace window.  ``TPUFRAME_TRACE_STEPS="<start>:<count>"``
    # (absolute step indices) captures a jax.profiler trace of exactly
    # those steps; the legacy ``--trace-dir``-only invocation keeps its
    # historical window (start_step+5, 3 steps).  The window is announced
    # as typed trace_start/trace_end events carrying the artifact path,
    # so the offline analyzer can join profile artifacts to the steps
    # they cover.
    trace_window = parse_trace_steps(os.environ.get("TPUFRAME_TRACE_STEPS"))
    if trace_window is None and trace_dir is not None:
        trace_window = (h.start_step + 5, 3)
    events_dir = os.environ.get(events_lib.ENV_DIR, "").strip()
    trace_path = trace_dir or (os.path.join(events_dir, "trace")
                               if events_dir else "trace")

    def _trace_end(at_step: int) -> None:
        nonlocal t_trace
        if t_trace is None:
            return
        try:
            t_trace.__exit__(None, None, None)
        except Exception:  # noqa: BLE001 — profiling must not kill the run
            pass
        t_trace = None
        events_lib.emit("trace_end", step=at_step, path=trace_path)

    t_trace = None
    while step < cfg.total_steps:
        if (trace_window is not None and t_trace is None
                and step == trace_window[0]):
            try:
                ctx = profile_trace(trace_path)
                ctx.__enter__()
            except Exception:  # noqa: BLE001 — profiler unavailable: the
                trace_window = None  # run goes on untraced
            else:
                t_trace = ctx
                events_lib.emit("trace_start", step=step, path=trace_path)
        if (t_trace is not None
                and step >= trace_window[0] + trace_window[1]):
            _trace_end(step)
            trace_window = None  # one window per run

        t_step0 = time.perf_counter()
        with span("train.data_wait", step=step):
            batch = next(data_iter)
        t_compute0 = time.perf_counter()
        with span("train.step", step=step):
            state, metrics = h.train_step(state, batch)
        step += 1
        t_end = time.perf_counter()
        # Input wait is its own goodput bucket (arXiv:1909.09756's input
        # stall), NOT part of step time: a loader that can't keep up must
        # show as `input`, never masquerade as slow compute.
        input_wait_s = t_compute0 - t_step0
        step_s = t_end - t_compute0
        first_step = meter.first_step_s is None
        meter.charge("input", input_wait_s)
        meter.step(step_s)
        run_info["step"] = step
        is_log_step = step % cfg.log_every == 0 or step == cfg.total_steps
        fetched = None
        if events_lib.enabled():
            # Step event BEFORE the fault seam fires: a crash fault must
            # not erase the record of the step that preceded it.  Loss
            # rides along only on log steps — those device_get anyway, so
            # the event costs no extra host↔device sync.
            extra: dict = {}
            if is_log_step:
                fetched = jax.device_get(metrics)
                if "loss" in fetched:
                    extra["loss"] = float(fetched["loss"])
            events_lib.emit("step", step=step,
                            wall_ms=round(step_s * 1e3, 3),
                            input_wait_ms=round(input_wait_s * 1e3, 3),
                            examples=examples_per_step, **extra)
            if first_step:
                events_lib.emit("compile", step=step,
                                wall_ms=round(step_s * 1e3, 3),
                                source="first_step")
        faults_lib.set_step(step)
        faults_lib.fire("host")  # crash/signal faults, once per step
        if hang_step and step == hang_step:
            print(f"[tpuframe] FAULT INJECTION: hanging at step {step}",
                  flush=True)
            time.sleep(10 ** 6)
        rate.update(examples_per_step)
        heartbeat.beat(step)

        if is_log_step:
            metrics = fetched if fetched is not None \
                else jax.device_get(metrics)
            final_train_metrics = {k: float(v) for k, v in metrics.items()}
            r = rate.rate()
            if r is not None:
                final_train_metrics["examples_per_sec"] = r
                final_train_metrics["examples_per_sec_per_chip"] = rate.per_chip()
            # Retry-loop activity (resilience/policy.py) — empty unless the
            # storage layer actually retried, so clean runs log nothing new.
            final_train_metrics.update(obs_metrics.counters("retry."))
            final_train_metrics.update(
                obs_metrics.counters("compile_cache."))
            logger.log(step, final_train_metrics)
            if exporter is not None:
                exporter.set_gauge("tpuframe_step", step)
                exporter.set_gauge("tpuframe_step_time_ms", step_s * 1e3)
                exporter.set_gauge("tpuframe_input_wait_ms",
                                   input_wait_s * 1e3)
                if r is not None:
                    exporter.set_gauge("tpuframe_examples_per_sec", r)
                exporter.flush()  # keep the textfile fallback current

        if step % cfg.eval_every == 0 or step == cfg.total_steps:
            h.state = state
            t_eval0 = time.perf_counter()
            with rate.paused():  # eval time isn't training throughput
                with span("train.eval", step=step):
                    eval_metrics = evaluate(h, cfg.eval_batches)
            meter.charge("eval", time.perf_counter() - t_eval0)
            logger.log(step, eval_metrics, prefix="eval")
            final_train_metrics.update(
                {f"eval_{k}": v for k, v in eval_metrics.items()})
            if (cfg.track_best and h.manager is not None
                    and "loss" in eval_metrics):
                if h.manager.save_best(step, state,
                                       float(eval_metrics["loss"])):
                    if bootstrap.is_primary():
                        print(f"[tpuframe] new best eval loss "
                              f"{eval_metrics['loss']:.4f} at step {step}",
                              flush=True)
            heartbeat.beat(step)  # eval (incl. its first compile) is progress

        if h.manager is not None:
            will_save = h.manager.should_save(step)
            t_ckpt0 = time.perf_counter()
            with rate.paused():
                if will_save:
                    with span("train.checkpoint", step=step):
                        h.manager.maybe_save(step, state)
                heartbeat.beat(step)  # a long blocking save is progress too
            if will_save:
                meter.charge("ckpt", time.perf_counter() - t_ckpt0)

        if guard.requested:
            # Preemption contract: commit a final checkpoint at this step
            # boundary and exit rc 14 so the supervisor resumes (no crash
            # charged, no backoff) instead of losing up to ckpt_every steps.
            if h.manager is not None:
                t_ckpt0 = time.perf_counter()
                if not h.manager.should_save(step):  # else just saved above
                    h.manager.save(step, state)
                # Deadline-bounded drain, not an open-ended join: the
                # SIGTERM grace window is finite, and flush() guarantees
                # every pending save is committed or quarantined before
                # rc 14 tells the supervisor "resume me" — never
                # acknowledged-but-unwritten.
                flushed = h.manager.flush(deadline_s=float(os.environ.get(
                    "TPUFRAME_FLUSH_DEADLINE_S", "60")))
                if not flushed and bootstrap.is_primary():
                    print("[tpuframe] flush deadline expired — in-flight "
                          "save quarantined; resume uses the previous "
                          "committed step", flush=True)
                meter.charge("ckpt", time.perf_counter() - t_ckpt0)
            heartbeat.stop()
            _trace_end(step)
            if timeline is not None:
                timeline.instant("preempted", step=step)
                timeline.close()
            if run_info["devmem"] is not None:
                run_info["devmem"].stop()
            _emit_run_end(step)
            events_lib.close()
            logger.close()
            exporter_lib.stop()
            guard.uninstall()
            if bootstrap.is_primary():
                print(f"[tpuframe] preempted ({guard.signal_name}): "
                      f"checkpoint committed at step {step}; exiting rc "
                      f"{RC_PREEMPTED} for supervisor resume", flush=True)
            raise SystemExit(RC_PREEMPTED)

    _trace_end(step)
    t_ckpt0 = time.perf_counter()
    if h.manager is not None and step % cfg.ckpt_every != 0:
        h.manager.save(step, state)  # final state always durable
    if h.manager is not None:
        h.manager.wait_pending()  # async saves must commit before exit
        meter.charge("ckpt", time.perf_counter() - t_ckpt0)
    heartbeat.stop()
    if timeline is not None:
        timeline.close()
        if bootstrap.is_primary():
            print(f"[tpuframe] step timeline written to {timeline.path}",
                  flush=True)
    logger.close()
    if run_info["devmem"] is not None:
        run_info["devmem"].stop()
    _emit_run_end(step)
    events_lib.close()
    flight_lib.uninstall()
    # Exporter goes down last: the final scrape (and the textfile flush
    # inside stop()) reflects the completed run's books.
    exporter_lib.stop()
    guard.uninstall()
    final_train_metrics["step"] = step
    final_train_metrics.update(obs_metrics.counters("retry."))
    final_train_metrics.update(obs_metrics.counters("compile_cache."))
    return final_train_metrics


def _parse_set(values: list[str]) -> dict:
    out: dict = {}
    for item in values:
        key, _, raw = item.partition("=")
        if not raw:
            raise ValueError(f"--set needs key=value, got {item!r}")
        try:
            value = ast.literal_eval(raw)
        except (ValueError, SyntaxError):
            value = raw
        # A repeated dict-valued key merges, as with_overrides merges it
        # into the config's own: a later --set model_kwargs= adds to an
        # earlier one instead of dropping it.
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            value = {**out[key], **value}
        out[key] = value
    return out


def main(argv: list[str] | None = None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", required=True,
                   help="workload name (see tpuframe.utils.config.WORKLOADS)")
    p.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                   help="override any TrainConfig field")
    p.add_argument("--data-dir", default=None)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--log-file", default=None)
    p.add_argument("--trace-dir", default=None,
                   help="capture an XLA profiler trace of a few steps")
    p.add_argument("--events-dir", default=None,
                   help="write structured run events "
                        "(events.<host>.jsonl; same as TPUFRAME_EVENTS_DIR)")
    args = p.parse_args(argv)
    if args.events_dir:
        # Via the env so every layer (ckpt, resilience, compile_cache,
        # supervisor-relaunched children) sees the same switch.
        os.environ[events_lib.ENV_DIR] = args.events_dir

    cfg = get_config(args.config)
    overrides = _parse_set(args.set)
    if args.data_dir:
        overrides["data_dir"] = args.data_dir
    if args.ckpt_dir:
        overrides["ckpt_dir"] = args.ckpt_dir
    cfg = cfg.with_overrides(**overrides)
    t0 = time.time()
    metrics = train(cfg, trace_dir=args.trace_dir, log_file=args.log_file)
    if bootstrap.is_primary():
        print(f"[tpuframe] done in {time.time() - t0:.1f}s: "
              f"{ {k: round(v, 5) if isinstance(v, float) else v for k, v in metrics.items()} }",
              flush=True)
    return metrics


if __name__ == "__main__":
    main()
