"""What the once-a-loader cast costs on the chip's host: builds the ResNet
cell's harness as `benchmark/runners/train.py` does and prints the
`loader.cast_column` spans, the counter and the process's resident memory
before, at the peak and after.  Run from the root of the checkout to read
(the parent makes no such span and keeps float32 columns):
python perf/pr36/cast_span.py"""

import gc
import json
import os
import resource
import statistics
import sys
import time

sys.path.insert(0, os.getcwd())   # the checkout it is run from: either side


def rss_mb() -> float:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") / 1e6


def main() -> None:
    from tpuframe.obs import metrics, timeline
    from tpuframe.parallel.mesh import MeshSpec
    from tpuframe.train import build_harness
    from tpuframe.utils.config import TrainConfig

    with open("benchmark/configs/resnet50.json") as f:
        fields = dict(json.load(f)["program"])
    with open("benchmark/traffic/train_b256.json") as f:
        traffic = json.load(f)
    fields.update(traffic["job"])
    fields.update(traffic["program_fields"])
    fields["mesh"] = MeshSpec(**traffic["mesh"])
    fields["seed"] = 36
    out = {"rss_before_mb": rss_mb()}
    t = time.monotonic()
    h = build_harness(TrainConfig(**fields))
    out["build_harness_s"] = time.monotonic() - t
    out["rss_after_build_mb"] = rss_mb()
    gc.collect()
    out["rss_after_gc_mb"] = rss_mb()
    out["rss_peak_mb"] = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss / 1e3
    out["cast_column"] = [dict(s.args, ms=s.ms, thread=s.thread)
                          for s in timeline.spans("loader.cast_column", t0=t)]
    out["bytes_cast_once"] = metrics.counters("loader.").get(
        "loader.bytes_cast_once", 0)
    out["column_dtype"] = str(h.train_loader.dataset.columns["image"].dtype)
    t = time.monotonic()
    stream = iter(h.train_loader)
    for _ in range(24):                      # three epochs of eight
        next(stream)["image"].block_until_ready()
    for name in ("loader.gather", "loader.cast", "loader.put"):
        out[name + "_ms_p50"] = statistics.median(
            timeline.durations_ms(name, t))
    h.train_loader.close()
    h.eval_loader.close()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
