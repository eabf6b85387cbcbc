"""PR 34: one benchmark run of a cell through benchmark/run.py's own
``run_cell``, with the runner's per-step spans (data_wait, dispatch,
device_wait) kept and the slow ones printed: which part of a window a
stall sits in.  The result line is run.py's own."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import run as bench  # noqa: E402

kept = {}
_make = bench.make_context


def make_context(*a, **kw):
    kept["ctx"] = _make(*a, **kw)
    return kept["ctx"]


bench.make_context = make_context
ap = argparse.ArgumentParser()
ap.add_argument("--workload", default="trinity_mini.train_b1_s8192")
ap.add_argument("--seed", type=int, required=True)
args = ap.parse_args()
result = bench.run_cell(argparse.Namespace(
    workload=args.workload, seed=args.seed, seconds=20.0, trace=0))
spans = kept["ctx"].spans.spans
out = {"seed": args.seed, "correct": result["correct"],
       "attempted": result["attempted"],
       "rate": result["metrics"]["train_seq_per_s_per_chip"]["value"],
       "setup_s": result["metrics"]["setup_s"]["value"]}
for name in ("data_wait", "dispatch", "device_wait"):
    d = sorted(((b - a) * 1e3, i) for i, (a, b) in
               enumerate(spans.get(name, [])))
    out[name] = {"n": len(d), "median_ms": round(d[len(d) // 2][0], 2),
                 "longest": [[i, round(ms, 1)] for ms, i in d[-4:]]}
starts = [a for a, _ in spans.get("dispatch", [])]
gaps = sorted(((b - a) * 1e3, i) for i, (a, b) in
              enumerate(zip(starts, starts[1:])))
out["dispatch_to_dispatch"] = {"median_ms": round(gaps[len(gaps) // 2][0], 1),
                               "longest": [[i, round(ms, 1)]
                                           for ms, i in gaps[-4:]]}
from tpuframe.obs import metrics as obs  # noqa: E402

try:
    out["counters"] = {k: v for k, v in obs.counters("moe.").items()
                       if "load" not in k}
except Exception as e:  # noqa: BLE001 - a probe: the spans matter more
    out["counters"] = repr(e)
print(json.dumps(out), flush=True)
