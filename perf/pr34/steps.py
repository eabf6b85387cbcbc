"""PR 34: per-step times of trinity_mini.train_b1_s8192 on the chip, to
tell a slow run's cause: the host runs two steps ahead as the benchmark's
window does, and the time at which each step's loss is ready is kept."""

import argparse
import collections
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=150)
    args = ap.parse_args()
    ctx = bench.make_context(argparse.Namespace(
        workload="trinity_mini.train_b1_s8192", seed=args.seed, seconds=20,
        trace=0))
    runner = bench.load_module(os.path.join(ROOT, "benchmark", "runners",
                                            "train.py"))
    import gc

    pauses, started = [], {}

    def on_gc(phase, info):
        if phase == "start":
            started["t"] = time.monotonic()
        else:
            pauses.append((info["generation"],
                           time.monotonic() - started["t"]))

    gc.callbacks.append(on_gc)
    cell = runner.Cell(ctx)
    cell.setup()
    in_setup = len(pauses)
    step, it = cell.h.train_step, cell.it
    pending, ready, waits = collections.deque(), [], []
    t0 = time.monotonic()
    for _ in range(args.steps):
        t = time.monotonic()
        batch = next(it)
        waits.append(time.monotonic() - t)
        cell.state, metrics = step(cell.state, batch)
        pending.append(metrics["loss"])
        if len(pending) > 2:
            pending.popleft().block_until_ready()
            ready.append(time.monotonic() - t0)
    while pending:
        pending.popleft().block_until_ready()
        ready.append(time.monotonic() - t0)
    gaps = [b - a for a, b in zip(ready, ready[1:])]
    order = sorted(range(len(gaps)), key=lambda i: -gaps[i])
    from tpuframe.obs import metrics as obs

    c = obs.counters("moe.")
    print(json.dumps({
        "seed": args.seed, "steps": args.steps,
        "rate": (len(ready) - 1) / (ready[-1] - ready[0]),
        "gap_ms_median": 1e3 * sorted(gaps)[len(gaps) // 2],
        "gap_ms_min": 1e3 * min(gaps),
        "slowest": [[i, round(1e3 * gaps[i], 1)] for i in order[:12]],
        "data_wait_ms_max": 1e3 * max(waits),
        "counters": {k: v for k, v in c.items() if "load" not in k},
        "gc_in_setup": [[g, round(1e3 * d, 1)] for g, d in pauses[:in_setup]
                        if d > 0.05],
        "gc_in_loop": [[g, round(1e3 * d, 1)] for g, d in pauses[in_setup:]
                       if d > 0.005],
        "gc_counts": [len(pauses[:in_setup]), len(pauses[in_setup:])],
        "objects": len(gc.get_objects())}), flush=True)
    t = time.monotonic()
    gc.collect()
    print(json.dumps({"full_collect_s": time.monotonic() - t}), flush=True)
    cell.release()


if __name__ == "__main__":
    main()
