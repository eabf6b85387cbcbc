"""Readings that a cell's limits are set from, taken on the chip at the
cell's own size (run by hand through the chip tool; never by the driver):

    python benchmark/tests/readings_on_chip.py --workload <cell> --seeds 1,2,3

For a training cell, per seed of ``--program-seeds``: the program itself,
one harness built and driven through its first steps as a run's set-up
does, against the float32 reference (the lower reading, in one process
where a run each would cost its set-up and window).  Per seed of
``--seeds``: the reference in the next precision down
(int8, the control) and the reference over half of each batch (the planted
fault), each against the float32 reference, by the same numbers that
``correct`` compares.  The program is not needed for these: both sides are
the reference.  The batches come from the program's synthetic data set, as
the cell's do.

For a serving cell, per seed, in one process: the program over a short
window at the cell's own load, then the reference and the int8 control over
the same sample of finished requests: the served tokens' widest gap and the
control's.

One JSON line per seed.  ``--cpu-rehearsal`` runs the toy manifest.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import numpy as np  # noqa: E402

import run as bench  # noqa: E402

NO_LIMIT = {k: float("inf") for k in (
    "loss_step1_rel", "loss_step2_rel", "loss_step3_rel", "grad1_norm_gap",
    "delta3_norm_gap", "grad1_norm_gap_median", "delta3_norm_gap_median")}


def training_batches(ctx, seed: int) -> list:
    import jax.numpy as jnp

    from tpuframe.train import build_datasets
    from tpuframe.utils.config import TrainConfig

    fields = dict(ctx.config["program"])
    fields.update(ctx.traffic.get("program_fields", {}))
    fields = {k: v for k, v in fields.items()
              if k in ("name", "model", "dataset", "dataset_kwargs")}
    train_ds, _ = build_datasets(TrainConfig(**fields))
    b = int(ctx.traffic["job"]["global_batch"])
    order = np.random.default_rng(seed).permutation(len(train_ds))[:3 * b]
    cast = jnp.bfloat16 if ctx.config["dtype"] == "bfloat16" else None
    out = []
    for i in range(3):
        rows = train_ds[order[i * b:(i + 1) * b]]
        out.append({k: (jnp.asarray(v, cast) if cast is not None
                        and np.issubdtype(v.dtype, np.floating)
                        else jnp.asarray(v)) for k, v in rows.items()})
    return out


def training(ctx, seeds) -> None:
    runner = bench.load_module(os.path.join(BENCH, "runners", "train.py"))
    arch, job = ctx.config["arch"], ctx.traffic["job"]
    b = int(job["global_batch"])
    for seed in seeds:
        t0 = time.monotonic()
        weights = ctx.reference.init_weights(arch, seed)
        names = runner._paths_and_leaves(weights["params"])[0]
        batches = training_batches(ctx, seed)
        read = lambda **kw: runner.reference_readings(  # noqa: E731
            ctx.reference, arch, job, weights, batches, **kw)
        good = read()
        row = {"workload": ctx.cell["name"], "seed": seed,
               "ref_losses": good["losses"]}
        for name, kw in (("control_int8", {"quant": "int8"}),
                         ("fault_half_batch", {"keep_rows": b // 2})):
            low = read(**kw)
            cmp = runner.compare(low, good, NO_LIMIT)
            row[name] = {k: v["value"] for k, v in cmp.items()}
            row[name + "_look"] = runner.gap_report(low, good, names)
        row["seconds"] = time.monotonic() - t0
        print(json.dumps(row), flush=True)


def training_program(ctx, seeds) -> None:
    runner = bench.load_module(os.path.join(BENCH, "runners", "train.py"))
    ctx.traffic["limits"] = NO_LIMIT
    for seed in seeds:
        t0 = time.monotonic()
        ctx.seed = seed
        cell = runner.Cell(ctx)
        try:
            cell.setup()
        finally:
            cell.release()
        out = cell.check()
        print(json.dumps({
            "workload": ctx.cell["name"], "seed": seed,
            "program": {k: v["value"] for k, v in out["compared"].items()},
            "seconds": time.monotonic() - t0}), flush=True)


def serving(ctx, seeds, seconds: float) -> None:
    runner = bench.load_module(os.path.join(BENCH, "runners", "serve.py"))
    for seed in seeds:
        t0 = time.monotonic()
        ctx.seed, ctx.seconds = seed, seconds
        cell = runner.Cell(ctx)
        try:
            cell.setup()
            window = cell.measure()
        finally:
            cell.release()
        out = cell.check(quant="int8")
        print(json.dumps({
            "workload": ctx.cell["name"], "seed": seed,
            "program_gap_max": out["compared"]["served_token_gap_max"][
                "value"],
            "control_int8_gap_max": out["control_gap_max"],
            "failed": out["failed"], "attempted": out["attempted"],
            "end_to_end": window["end_to_end"],
            "seconds": time.monotonic() - t0}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    program_seeds = [int(s) for s in args.program_seeds.split(",") if s]
    ctx = bench.make_context(
        argparse.Namespace(workload=args.workload,
                           seed=(seeds + program_seeds)[0],
                           seconds=args.seconds, trace=0),
        require_chip=not args.cpu_rehearsal,
        manifest_path=os.path.join(HERE, "toy", "BENCHMARK.json")
        if args.cpu_rehearsal else None)
    if ctx.traffic["runner"] == "train":
        training_program(ctx, program_seeds)
        training(ctx, seeds)
    else:
        serving(ctx, seeds, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
