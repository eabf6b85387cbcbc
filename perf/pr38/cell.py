"""One run of a cell through ``benchmark/run.py``'s own ``run_cell``, from
the checkout in the working directory (this PR's tree or the parent's
unpacked beside it), then the program's ``remat.`` counters (absent on
the parent).  ``--no-reference`` skips the float32 reference after the
window, for rate-only runs: their ``correct`` is then not checked and is
printed as null.  The result line is printed last.

    python perf/pr38/cell.py --workload <cell> --seed <n> --seconds 20 --trace 0|1 [--no-reference]
"""

import argparse
import importlib.util
import json
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--no-reference", action="store_true")
    args = ap.parse_args()
    root = os.getcwd()
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(root, "benchmark", "run.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    if args.no_reference:
        load = bench.load_module

        def load_module(path):
            mod = load(path)
            if hasattr(mod, "Cell"):
                # the training runner counts steps, the serving one requests
                mod.Cell.check = lambda self: {
                    "correct": False,
                    "attempted": getattr(self, "steps_in_window",
                                         getattr(self, "attempted", 0)),
                    "failed": 0, "compared": {}}
            return mod
        bench.load_module = load_module
    result = bench.run_cell(args, root=root)
    if args.no_reference:
        result["correct"] = None
    from tpuframe.obs import metrics

    print(json.dumps({"counters": metrics.counters("remat.")}), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
