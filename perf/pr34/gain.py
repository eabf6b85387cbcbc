"""PR 34: does the rate of trinity_mini.train_b1_s8192 follow the seed when
the norms after the sub-layers start at a gain of 1 (the calibrated router
bias alone) and not at the reference's POST_NORM_SCALE?  One process, one
harness; for each gain and seed the weights go in the program's place as
the runner puts them, the optimizer's state back to zeros, and the
runner's loop (two steps ahead) runs ``--steps`` steps after five warm
ones.  A line a run: rate, the slowest steps, the routing counters."""

import argparse
import collections
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import run as bench  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--gains", type=float, nargs="+", default=[1.0, 0.02])
    ap.add_argument("--steps", type=int, default=70)
    ap.add_argument("--toy", action="store_true",
                    help="the toy cell on the CPU: a rehearsal")
    args = ap.parse_args()
    toy = os.path.join(ROOT, "benchmark", "tests", "toy",
                       "BENCHMARK.trinity.json")
    ctx = bench.make_context(
        argparse.Namespace(
            workload="trinity_toy.toy_train_b1_s64" if args.toy
            else "trinity_mini.train_b1_s8192",
            seed=args.seeds[0], seconds=20, trace=0),
        require_chip=not args.toy, manifest_path=toy if args.toy else None)
    runner = bench.load_module(os.path.join(ROOT, "benchmark", "runners",
                                            "train.py"))
    import jax
    import jax.numpy as jnp

    from tpuframe.obs import metrics as obs

    cell = runner.Cell(ctx)
    cell.setup()
    ref, step, it = ctx.reference, cell.h.train_step, cell.it
    place = lambda w, old: jax.device_put(w.astype(old.dtype),  # noqa: E731
                                          old.sharding)
    zeroed = jax.jit(lambda s: jax.tree.map(jnp.zeros_like, s),
                     donate_argnums=0)
    for gain in args.gains:
        ref.POST_NORM_SCALE = gain
        ref._make_weights.cache_clear()
        for seed in args.seeds:
            zero, cell.state = zeroed(cell.state), None
            weights = ref.init_weights(cell.arch, seed)
            state = dataclasses.replace(
                zero,
                params=jax.tree.map(place, weights["params"], zero.params),
                model_state=jax.tree.map(place, weights["model_state"],
                                         zero.model_state))
            del weights, zero
            for _ in range(5):
                state, metrics = step(state, next(it))
            float(metrics["loss"])
            pending, ready = collections.deque(), []
            t0 = time.monotonic()
            for _ in range(args.steps):
                state, metrics = step(state, next(it))
                pending.append(metrics["loss"])
                if len(pending) > runner.RUN_AHEAD:
                    pending.popleft().block_until_ready()
                    ready.append(time.monotonic() - t0)
            while pending:
                pending.popleft().block_until_ready()
                ready.append(time.monotonic() - t0)
            gaps = [b - a for a, b in zip(ready, ready[1:])]
            order = sorted(range(len(gaps)), key=lambda i: -gaps[i])
            c = obs.counters("moe.")
            load = [v for k, v in c.items() if k.startswith("moe.load.")]
            n = (args.steps + 5) * c["moe.layers"]
            print(json.dumps({
                "gain": gain, "seed": seed,
                "rate": (len(ready) - 1) / (ready[-1] - ready[0]),
                "gap_ms_median": 1e3 * sorted(gaps)[len(gaps) // 2],
                "slowest_ms": [[i, round(1e3 * gaps[i], 1)]
                               for i in order[:5]],
                "rows_here_a_layer": c["moe.rows_here"] / n,
                "rows_looped": c["moe.rows_looped"],
                "load_max_over_mean": max(load) * len(load) / sum(load),
                "loss": float(metrics["loss"])}), flush=True)
            cell.state = state
            del state


if __name__ == "__main__":
    main()
