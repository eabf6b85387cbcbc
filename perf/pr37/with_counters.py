"""One run of a cell through ``benchmark/run.py``'s own ``run_cell``, in
this process, and then the program's routing counters as they stood at the
window's last step (``moe.rows_looped`` is no metric of the benchmark's,
and the acceptance asks for it).  The result line is the benchmark's own,
printed last.

    python perf/pr37/with_counters.py --workload <cell> --seed <n> --seconds 20 --trace 1
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import run as bench  # noqa: E402


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    result = bench.run_cell(args, root=ROOT)
    from tpuframe.obs import metrics

    c = metrics.counters("moe.")
    load = [v for k, v in sorted(c.items()) if k.startswith("moe.load.")]
    print(json.dumps({"moe": {k: v for k, v in c.items()
                              if not k.startswith("moe.load.")},
                      "load_held_here": load[:8], "load_max": max(load),
                      "load_sum": sum(load)}), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
