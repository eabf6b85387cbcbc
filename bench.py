"""Benchmark: ResNet-50 training throughput, images/sec/chip.

The driver-defined metric (BASELINE.json:2): ResNet-50 images/sec/chip.
This runs the flagship model's full training step (fwd+bwd+update, bf16
compute) on the attached TPU chip(s) with synthetic ImageNet shapes, which
isolates accelerator throughput from input-pipeline effects.

It measures on a TPU or it fails: a backend that is not ``tpu``, a TPU
whose ``device_kind`` is not in the peak table (tune/roofline.py), or any
error in the run is a non-zero exit with no result line.  Nothing is
retried smaller and nothing is reported from an earlier run.

``vs_baseline``: the reference's own numbers are unpublished (BASELINE.md —
`"published": {}` and the source mount was empty), so the anchor is the
Horovod-GPU era per-chip figure for this exact workload: ~360 images/sec on a
V100 with standard fp16/32 ResNet-50 training (MLPerf v0.6-era single-GPU
throughput; the Horovod paper's hardware class, PAPERS.md:8).
vs_baseline = value / 360.0.

Output: progress on stderr, then one JSON line on stdout
  {"metric": "resnet50_images_per_sec_per_chip", "value": N,
   "unit": "images/sec/chip", "vs_baseline": N, "mfu": N,
   "device": {"platform": "tpu", "kind": "...", "count": N}, ...}
"""

from __future__ import annotations

import json
import os
import sys
import time

V100_HOROVOD_ANCHOR = 360.0  # images/sec/chip, see module docstring

BATCH_PER_CHIP = int(os.environ.get("TPUFRAME_BENCH_BATCH", "256"))
IMAGE_SIZE = 224
WARMUP_STEPS = int(os.environ.get("TPUFRAME_BENCH_WARMUP", "3"))
MEASURE_STEPS = int(os.environ.get("TPUFRAME_BENCH_STEPS", "16"))

# XLA-counted (FMA = 2 flops, matching how the peak specs count):
# 1.252e13 flops / 512 images from the compiled full step's cost_analysis
# (perf/exp_breakdown.py; fwd alone is 4.08e12/512 = ~8.0e9, bwd+update the
# rest).  The literature's "4.1 GFLOPs" for ResNet-50 is GMACs.
RESNET50_FLOPS_PER_IMAGE = 1.252e13 / 512

_T0 = time.time()


def _log(msg: str) -> None:
    print(f"[bench +{time.time() - _T0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


def run(batch_per_chip: int, warmup: int, measure: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpuframe import elastic, mem, models
    from tpuframe.models import losses
    from tpuframe.parallel import mesh as mesh_lib
    from tpuframe.parallel import step as step_lib
    from tpuframe.parallel import zero1 as zero1_lib
    from tpuframe.tune import db as tune_db
    from tpuframe.tune import roofline
    from tpuframe.utils import xla_opts as xla_opts_lib

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py measures on a TPU; jax found "
                         f"{dev.platform!r} ({dev.device_kind})")
    # Unknown kind -> KeyError: a device outside the peak table has no MFU.
    generation, gen_source = roofline.device_generation(dev)
    peak = roofline.get_hardware(generation).bf16_flops

    # World resolution through the elastic resolver — the single source
    # of truth shared with train.build_harness, read at call time (never
    # cached at module level; TF116 enforces the discipline).
    world = elastic.current_world()
    n_chips = world.n_devices
    mesh = world.mesh
    _log(f"devices: {n_chips} x {dev.device_kind} (generation {generation}, "
         f"from {gen_source})")

    global_batch = batch_per_chip * n_chips
    program = f"train_resnet50_b{global_batch}"

    # A/B knobs, each env > tuning DB (TPUFRAME_TUNE_GEN only) > default:
    # TPUFRAME_BENCH_STEM=space_to_depth, TPUFRAME_BENCH_BN=folded,
    # TPUFRAME_REMAT_POLICY, TPUFRAME_WEIGHT_UPDATE=zero1,
    # TPUFRAME_XLA_OPTS="k=v,k=v".
    stem = os.environ.get("TPUFRAME_BENCH_STEM", "conv")
    bn = os.environ.get("TPUFRAME_BENCH_BN", "flax")
    remat_policy, remat_source = mem.resolve(
        program=program, family="remat_resnet50")
    weight_update, wu_source = zero1_lib.resolve(
        program=program, family="weight_update_resnet50")
    if mesh is None and weight_update == "zero1" and wu_source != "env":
        # single-chip run: nothing to shard the update over — a DB row
        # must never break a run, but an explicit env ask gets
        # make_train_step's error.
        weight_update = "replicated"
    try:
        xla_opts = xla_opts_lib.from_env()
    except ValueError as e:
        raise SystemExit(str(e))
    if xla_opts is None:
        xla_opts = tune_db.resolve_xla_opts(
            f"bench_resnet50_b{batch_per_chip}", family="bench_resnet50")
    _log(f"remat={remat_policy} ({remat_source}) weight_update="
         f"{weight_update} xla_opts={xla_opts}")

    model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16, stem=stem,
                            bn=bn)
    rng = np.random.default_rng(0)
    # bf16 on the host: halves infeed bytes and skips the on-device cast.
    x = rng.normal(0.5, 0.25, size=(global_batch, IMAGE_SIZE, IMAGE_SIZE, 3)
                   ).astype(jnp.bfloat16)
    y = rng.integers(0, 1000, size=(global_batch,)).astype(np.int32)
    variables = model.init(jax.random.key(0), jnp.asarray(x[:2]))

    tx = optax.sgd(0.1, momentum=0.9, nesterov=True)

    def loss_fn(params, model_state, batch, step_rng):
        logits, mutated = model.apply(
            {"params": params, **model_state}, batch["image"], train=True,
            mutable=["batch_stats"])
        loss = losses.softmax_cross_entropy(logits, batch["label"],
                                            label_smoothing=0.1)
        return loss, (dict(mutated), {})

    model_state = {"batch_stats": variables["batch_stats"]}
    state = step_lib.TrainState.create(variables["params"], tx,
                                       model_state=model_state)
    train_step = step_lib.make_train_step(
        loss_fn, tx, mesh, donate=True, compiler_options=xla_opts,
        remat_policy=None if remat_policy == "none" else remat_policy,
        weight_update=weight_update)

    if mesh is not None:
        if weight_update == "zero1":
            state = zero1_lib.make_state(variables["params"], tx, mesh,
                                         model_state=model_state)
        else:
            state = step_lib.replicate_state(state, mesh)
        put = lambda a: jax.device_put(a, mesh_lib.batch_sharding(mesh))  # noqa: E731
    else:
        put = jax.device_put
    batch = {"image": put(x), "label": put(y)}

    _log(f"compiling + warmup ({warmup} steps, batch {batch_per_chip}/chip, "
         f"global {global_batch})...")
    for i in range(warmup):
        state, metrics = train_step(state, batch)
        float(metrics["loss"])  # per-step sync is fine for warmup
        _log(f"warmup step {i + 1}/{warmup} done")

    # Timing: async chained dispatch with a scalar fetch every SYNC_EVERY
    # steps.  Each step consumes the previous state, so fetching step k's
    # loss is a full barrier for steps 1..k — honest wall-clock — while the
    # host runs ahead and dispatch overlaps device compute (the production
    # loop's behavior).
    sync_every = 8
    _log(f"measuring {measure} steps (sync every {sync_every})...")
    t0 = time.perf_counter()
    done = 0
    while done < measure:
        chunk = min(sync_every, measure - done)
        for _ in range(chunk):
            state, metrics = train_step(state, batch)
        float(metrics["loss"])  # barrier for the whole chunk
        done += chunk
    dt = time.perf_counter() - t0

    per_chip = measure * global_batch / dt / n_chips
    _log(f"measured {per_chip:.1f} images/sec/chip "
         f"({dt / measure * 1e3:.1f} ms/step)")
    line = {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / V100_HOROVOD_ANCHOR, 4),
        "mfu": round(per_chip * RESNET50_FLOPS_PER_IMAGE / peak, 4),
        "chip": generation,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": n_chips},
        "n_chips": n_chips,
        "batch_per_chip": batch_per_chip,
        "ms_per_step": round(dt / measure * 1e3, 2),
    }
    if remat_policy != "none":
        line["policy"] = remat_policy
    if weight_update != "replicated":
        line["weight_update"] = weight_update
    return line


def main() -> None:
    from tpuframe.utils import compile_cache

    compile_cache.enable()
    print(json.dumps(run(BATCH_PER_CHIP, WARMUP_STEPS, MEASURE_STEPS)),
          flush=True)


if __name__ == "__main__":
    main()
