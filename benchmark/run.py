"""The benchmark's one command.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Knows no cell, configuration or metric by name: it finds the cell in
``BENCHMARK.json``, its configuration file, its traffic file
(``traffic/<mix>.json``), the runner the traffic file names
(``runners/<kind>.py``), the configuration's plain reference
(``reference/<name>.py``) and, for a traced run, one reader per per-layer
metric (``layer_metrics/<name>.py``), each looked for under the manifest's
``paths`` in order.  A later PR adds files and entries and edits none.

It measures on a TPU or exits non-zero with no result line.  Set-up (load,
weights from the seed on the device, warm-up of the cell's own shapes, a
serving mix's lead-in) is timed apart as ``setup_s``; the window lasts ``--seconds``; then the peak
memory is read, the program's state is freed and the plain reference
decides ``correct``.  The last line of standard output is the result; the
numbers compared stand beside their limits as the last lines of standard
error and under the result's last key, ``compared``.
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T_PROCESS:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


class Refused(SystemExit):
    """The run cannot measure: exit code 2, no result line."""

    def __init__(self, why: str):
        print(f"benchmark: {why}", file=sys.stderr, flush=True)
        super().__init__(2)


# --------------------------------------------------------------------------
# finding files by name
# --------------------------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_file(manifest: dict, root: str, *parts: str) -> str:
    """``<root>/<path>/<parts...>`` for the first of the manifest's
    ``paths`` that has it."""
    for base in manifest["paths"]:
        cand = os.path.join(root, base, *parts)
        if os.path.exists(cand):
            return cand
    raise FileNotFoundError(
        f"{os.path.join(*parts)} under none of {manifest['paths']}")


def find_reader(manifest: dict, root: str, metric: str) -> str:
    """``layer_metrics/<metric>.py``; a quantity split over cells that
    report different end-to-end metrics (``<quantity>.<cells>``) is read
    by ``<quantity>.py`` unless it has a reader of its own."""
    try:
        return find_file(manifest, root, "layer_metrics", metric + ".py")
    except FileNotFoundError:
        if "." not in metric:
            raise
        return find_file(manifest, root, "layer_metrics",
                         metric.rsplit(".", 1)[0] + ".py")


def load_module(path: str):
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in
                              os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(manifest: dict, group: str, cell: str) -> list[dict]:
    """The metrics of ``group`` that ``cell`` reports: those that list it
    under ``workloads``; an end-to-end metric with no list is every
    cell's, a per-layer metric with no list is reported by every cell that
    reports the end-to-end metric it moves."""
    def listed(m):
        return "workloads" not in m or cell in m["workloads"]

    e2e = {m["name"] for m in manifest["end_to_end"] if listed(m)}
    if group == "end_to_end":
        return [m for m in manifest[group] if m["name"] in e2e]
    return [m for m in manifest[group] if listed(m) and m["moves"] in e2e]


# --------------------------------------------------------------------------
# the context a runner works in
# --------------------------------------------------------------------------

class Spans:
    """Host spans of one run, kept in memory.  A span is also
    a ``TraceAnnotation`` named ``bench:<name>``, so that the device trace
    can say what the host was doing in an idle gap."""

    def __init__(self):
        self.spans: dict[str, list[tuple[float, float]]] = {}
        self._annotation = None  # jax.profiler's, looked up on first use

    @contextlib.contextmanager
    def span(self, name: str):
        if self._annotation is None:
            import jax

            self._annotation = jax.profiler.TraceAnnotation
        t0 = time.monotonic()
        with self._annotation("bench:" + name):
            try:
                yield
            finally:
                self.spans.setdefault(name, []).append((t0, time.monotonic()))

    def mark(self) -> dict:
        return {k: len(v) for k, v in self.spans.items()}

    def since(self, mark: dict) -> dict:
        return {k: v[mark.get(k, 0):] for k, v in self.spans.items()}


class Tracer:
    """The profiler around a part of the window, only in a traced run."""

    def __init__(self, enabled: bool, out_dir: str):
        self.enabled, self.out_dir = enabled, out_dir
        self.active = False

    def start(self) -> None:
        if not self.enabled or self.active:
            return
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        # device ops and the runner's own spans; no Python call stacks and
        # no HLO protos, which make the trace large and slow to write
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        self.active = True

    def stop(self) -> None:
        if not self.active:
            return
        import jax

        jax.profiler.stop_trace()
        self.active = False


class CompileCounter:
    def __init__(self):
        self.lowerings = self.compiles = 0
        self.on = False

    def install(self) -> None:
        import jax

        def listen(event: str, duration: float, **kw) -> None:
            if not self.on:
                return
            if event == LOWER_EVENT:
                self.lowerings += 1
            elif event == COMPILE_EVENT:
                self.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(listen)

    @contextlib.contextmanager
    def window(self):
        self.on = True
        try:
            yield
        finally:
            self.on = False


class Context:
    def __init__(self, *, manifest, root, cell, config, traffic, seed,
                 seconds, trace, reference, flops, peaks, device, loadgen):
        self.manifest, self.root, self.cell = manifest, root, cell
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.reference, self.flops, self.peaks = reference, flops, peaks
        self.device, self.loadgen = device, loadgen
        self.chips = int(cell["chips"])
        self.spans = Spans()
        self.compiles = CompileCounter()
        self.tracer = Tracer(trace, os.path.join(
            root, ".bench_trace", cell["name"]))
        self.log = log


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

def setup_cache(root: str) -> str:
    """JAX's persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else the fixed ``<checkout>/
    .xla_cache`` (the path the program's own helper also takes).  Every
    program is kept, however quickly it compiled, so that a second run
    compiles nothing."""
    os.environ.setdefault("TPUFRAME_COMPILE_CACHE_MIN_S", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    where = env or os.path.join(root, ".xla_cache")
    if not env:
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def device_report(chips: int, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise Refused(f"jax found no TPU (platform {devs[0].platform!r}); "
                      f"a device metric comes only from a chip")
    if require_chip and len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, jax found "
                      f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs) if not require_chip else chips}


def memory_peak_bytes(chips: int):
    """The peak on the fullest chip, from ``Device.memory_stats()``: the
    peak of the arrays in use plus the peak of what the runtime reserved
    for the compiled programs' own temporaries.  On the v5e the second is
    kept apart from the first (``peak_bytes_reserved``): the ResNet step's
    9.0 GB of temporaries show only there."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        log(f"memory_stats {d.id}: " + ", ".join(
            f"{k}={stats[k]}" for k in ("bytes_in_use", "peak_bytes_in_use",
                                        "bytes_reserved",
                                        "peak_bytes_reserved") if k in stats))
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"])
                         + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else None


def make_context(args, *, require_chip: bool = True, manifest_path=None,
                 root: str = ROOT) -> Context:
    """Find the cell's files and build the context its runner works in."""
    manifest = load_json(manifest_path or os.path.join(root,
                                                       "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        raise Refused(f"no workload {args.workload!r} in the manifest")
    cell = cells[args.workload]
    conf_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, conf_entry["file"]))
    traffic = load_json(find_file(manifest, root, "traffic",
                                  cell["traffic"] + ".json"))
    if root not in sys.path:
        sys.path.insert(0, root)
    try:
        import tpuframe  # noqa: F401 — the system under test
    except ImportError as e:
        raise Refused(f"the system under test is not in this directory: {e}")

    cache_dir = setup_cache(root)
    device = device_report(int(cell["chips"]), require_chip)
    log(f"device {device}; compile cache {cache_dir}")
    flops = load_module(find_file(manifest, root, "flops.py"))
    peaks = flops.peaks_for(device["kind"]) if require_chip else None
    reference = load_module(find_file(
        manifest, root, "reference",
        config.get("reference", cell["config"]) + ".py"))
    ctx = Context(manifest=manifest, root=root, cell=cell, config=config,
                  traffic=traffic, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), reference=reference, flops=flops,
                  peaks=peaks, device=device, loadgen=load_module(
                      find_file(manifest, root, "loadgen.py")))
    ctx.compiles.install()
    return ctx


def run_cell(args, *, require_chip: bool = True, manifest_path=None,
             root: str = ROOT) -> dict:
    """One run of one cell; returns the result object.  ``require_chip``
    is False only in the CPU rehearsals under ``tests/``, whose result
    carries the CPU's name in ``device`` and is never a measurement."""
    ctx = make_context(args, require_chip=require_chip,
                       manifest_path=manifest_path, root=root)
    manifest, cell, config, traffic = (ctx.manifest, ctx.cell, ctx.config,
                                       ctx.traffic)
    flops, peaks, device = ctx.flops, ctx.peaks, ctx.device
    runner = load_module(find_file(manifest, root, "runners",
                                   traffic["runner"] + ".py"))
    prog = runner.Cell(ctx)
    try:
        prog.setup()
        setup_s = time.monotonic() - _T_PROCESS
        log(f"set-up done in {setup_s:.2f} s; window of {args.seconds} s")
        with ctx.compiles.window():
            window = prog.measure()
        if "opened_at" in window:
            # a runner that leads its traffic in before the window opens
            setup_s = window["opened_at"] - _T_PROCESS
            log(f"the window opened {setup_s:.2f} s after the start")
        ctx.tracer.stop()
        log(f"window closed: lowerings_in_window={ctx.compiles.lowerings} "
            f"compiles_in_window={ctx.compiles.compiles}")
        mem_peak = memory_peak_bytes(ctx.chips)
    finally:
        prog.release()
    checked = prog.check()

    trace_summary = None
    if ctx.trace:
        trace_reduce = load_module(find_file(manifest, root,
                                             "trace_reduce.py"))
        trace_summary = trace_reduce.reduce_trace(
            ctx.tracer.out_dir, find_file(manifest, root,
                                          "op_categories.json"))
        shutil.rmtree(ctx.tracer.out_dir, ignore_errors=True)

    metrics: dict = {}
    if not ctx.trace:
        values = dict(window["end_to_end"], setup_s=setup_s)
        for m in cell_metrics(manifest, "end_to_end", cell["name"]):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        run = {"window": window, "spans": ctx.spans.spans,
               "trace": trace_summary,
               "cell": cell, "config": config, "traffic": traffic,
               "peaks": peaks, "flops": flops, "setup_s": setup_s}
        for m in cell_metrics(manifest, "per_layer", cell["name"]):
            value = load_module(find_reader(manifest, root,
                                            m["name"])).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device["memory_peak_bytes"] = mem_peak
    result = {"correct": bool(checked["correct"]),
              "attempted": int(checked["attempted"]),
              "failed": int(checked["failed"]),
              "metrics": metrics, "device": device}
    if ctx.trace and trace_summary is not None:
        device["busy_s"] = trace_summary["busy_s"]
        device["window_s"] = trace_summary["window_s"]
        result["breakdown"] = {"device_ops": trace_summary["device_ops"],
                               "idle_gaps": trace_summary["idle_gaps"]}
    result["lowerings_in_window"] = ctx.compiles.lowerings
    result["compared"] = checked["compared"]
    return result


def main(argv=None, *, require_chip: bool = True, manifest_path=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args, require_chip=require_chip,
                      manifest_path=manifest_path)
    sys.stdout.flush()
    for name, pair in result["compared"].items():
        print(f"compared {name} = {pair['value']!r} (limit {pair['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
