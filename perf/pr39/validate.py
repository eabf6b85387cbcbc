"""One ``--trace 1`` run of a cell through ``benchmark/run.py``'s own
``run_cell``, from the checkout in the working directory, that keeps the
profiler's trace long enough to set the ring's device intervals beside it:

* the ring's gap share over the runner's ``traced`` span, beside the
  trace's ``device_idle_share.*`` of the same span;
* the clock mapping: each ``tpuframe:<span>`` annotation of the trace
  (``--span``; absolute time = the ``Task Environment`` plane's
  ``profile_start_time`` + ``start_ns``) against the same ring span's
  ``t0`` put on the profiler's clock by the newest ``clock`` record;
* the watcher's lateness: each ``device.*`` interval's end against the
  end of the last ``XLA Modules`` event on the device that ends before it;
* the untraced window's gaps by innermost span, the ring's records a
  second in it, and how far back the ring still reaches.

``--no-reference`` skips the float32 reference (``correct`` is then
null).  Prints one JSON line of its own, then the result line; writes both
under ``chiprun_out/pr39/``.

    python perf/pr39/validate.py --workload <cell> --seed <n> --seconds 20 \\
        --span engine.decode.fetch [--no-reference] [--cpu --manifest m.json]
"""

import argparse
import bisect
import glob
import importlib.util
import json
import os
import shutil
import statistics
import sys
import types


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _quant(xs):
    if not xs:
        return None
    xs = sorted(xs)
    return {"n": len(xs), "min": xs[0], "median": statistics.median(xs),
            "max": xs[-1]}


# The split of the device's gaps by the innermost host span open over each
# gap on the thread that launched the work (the interval after the gap
# names it, ``by``; the last gap of the window takes the one before), as
# ``trace_reduce`` gives its gaps to the innermost span.  Read by hand
# beside a trace; no metric reads it.
NO_SPAN = "(no span)"
# intervals the program adds with ``record()`` on a thread, rather than
# opening them there: they cross the edges of the spans they overlap
RECORDED = ("sched.queue", "clock")


def innermost(spans) -> list:
    """``[(a, b, name)]``, in order: where some of ``spans`` (one thread's,
    nested as a thread's spans are) is open, the innermost one's name."""
    out, stack, cursor = [], [], None

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1].t1 <= t:
            top = stack.pop()
            if top.t1 > cursor:
                out.append((cursor, top.t1, top.name))
            cursor = top.t1

    for s in sorted(spans, key=lambda s: (s.t0, -s.t1)):
        if cursor is not None:
            close_until(s.t0)
        if stack and s.t0 > cursor:
            out.append((cursor, s.t0, stack[-1].name))
        cursor = s.t0
        stack.append(s)
    close_until(float("inf"))
    return out


def gap_by_span(gaps_mod, run: dict):
    """``({innermost span name: seconds of gap}, window_s)`` over the
    window ``run`` gives; None where the ring has no device interval."""
    found = gaps_mod.ring_window(run)
    if found is None:
        return None
    timeline, t0, t1 = found
    ring = timeline.spans()
    device = sorted((s for s in ring if s.name.startswith("device.")),
                    key=lambda s: s.t0)
    if not device:
        return None
    t0 = min(max(t0, ring[0].t1), t1)
    busy = gaps_mod._union([(s.t0, s.t1) for s in device], t0, t1)
    edges = [t0] + [t for iv in busy for t in iv] + [t1]
    starts = [s.t0 for s in device]
    gaps = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            k = bisect.bisect_left(starts, b)
            gaps.append((a, b, device[min(k, len(device) - 1)].args.get(
                "by")))
    host = [s for s in ring if s.thread != "device" and s.t1 > s.t0
            and s.name not in RECORDED and s.t1 > t0 and s.t0 < t1]
    segments = {th: innermost([s for s in host if s.thread == th])
                for th in {th for _, _, th in gaps}}
    ends = {th: [sb for _, sb, _ in seg] for th, seg in segments.items()}
    out: dict = {}
    for a, b, th in gaps:
        left = b - a
        seg = segments[th]
        for i in range(bisect.bisect_right(ends[th], a), len(seg)):
            sa, sb, name = seg[i]
            if sa >= b:
                break
            part = min(b, sb) - max(a, sa)
            out[name] = out.get(name, 0.0) + part
            left -= part
        if left > 0.0:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + left
    return out, t1 - t0


def trace_readings(trace_dir, ring, clock, span_name, t_lo, t_hi):
    import jax

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return {"trace": None}
    data = jax.profiler.ProfileData.from_file(found[-1])
    start = None
    annots, modules, lines = [], [], {}
    for plane in data.planes:
        pname = str(plane.name)
        if pname == "Task Environment":
            start = int(dict(plane.stats)["profile_start_time"])
        for line in plane.lines:
            lines.setdefault(pname, []).append(str(line.name))
            for ev in line.events:
                name = str(ev.name)
                if pname.startswith("/host:") and \
                        name == "tpuframe:" + span_name:
                    annots.append(ev.start_ns)
                elif pname.startswith("/device:") and \
                        str(line.name) == "XLA Modules":
                    modules.append((ev.start_ns, ev.start_ns
                                    + ev.duration_ns, name))
    if start is None:
        return {"trace": "no profile_start_time"}
    offset = clock["trace_ns"] - clock["monotonic_ns"]

    def on_trace(t):   # a ring time, in the trace's relative ns
        return 1e9 * t + offset - start

    ring_t0 = sorted(on_trace(s.t0) for s in ring
                     if s.name == span_name and t_lo <= s.t0 < t_hi)
    # each annotation against the ring span that starts nearest to it
    skew = []
    for a in annots:
        k = bisect.bisect_left(ring_t0, a)
        near = [ring_t0[i] for i in (k - 1, k) if 0 <= i < len(ring_t0)]
        if near:
            skew.append(min((a - r for r in near), key=abs) * 1e-3)
    modules.sort(key=lambda m: m[1])
    ends = [m[1] for m in modules]
    late, names = [], {}
    # by record name: ring start - first module start, ring end - last
    # module end, of the modules that ended inside the interval
    lead, lag = {}, {}
    for s in sorted((s for s in ring if s.name.startswith("device.")),
                    key=lambda s: s.t0):
        a, b = on_trace(s.t0), on_trace(s.t1)
        if t_lo <= s.t1 < t_hi:
            k = bisect.bisect_right(ends, b) - 1
            if k >= 0:
                late.append((b - ends[k]) * 1e-3)
                names[modules[k][2]] = names.get(modules[k][2], 0) + 1
            lo = bisect.bisect_right(ends, a)
            mine = modules[lo:k + 1] if k >= 0 else []
            if mine:
                lead.setdefault(s.name, []).append(
                    (a - min(m[0] for m in mine)) * 1e-3)
                lag.setdefault(s.name, []).append(
                    (b - max(m[1] for m in mine)) * 1e-3)
    # the device's busy time by programs (the XLA Modules line) over the
    # traced span: what the ring's intervals stand for, where the trace's
    # own idle share takes the union of the ops, gaps between ops too
    lo_ns, hi_ns = on_trace(t_lo), on_trace(t_hi)
    merged = []
    for a, b, _ in sorted(modules):
        a, b = max(a, lo_ns), min(b, hi_ns)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    module_idle = 100.0 * (1.0 - sum(b - a for a, b in merged)
                           / (hi_ns - lo_ns))
    in_window = {}
    for m in modules:
        if on_trace(t_lo) <= m[0] < on_trace(t_hi):
            key = m[2].split("(")[0]
            in_window[key] = in_window.get(key, 0) + 1
    return {"xplane_lines": {k: sorted(set(v)) for k, v in lines.items()
                             if not k.startswith("/host:")},
            "annotations": len(annots), "ring_spans": len(ring_t0),
            "annotation_minus_ring_us": _quant(skew),
            "watcher_late_us": _quant(late),
            "late_against_module": names,
            "ring_start_minus_module_start_us": {
                k: _quant(v) for k, v in lead.items()},
            "ring_end_minus_module_end_us": {
                k: _quant(v) for k, v in lag.items()},
            "modules_in_traced_window": in_window,
            "module_idle_share_traced": module_idle,
            "clock_offset_ns": offset}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--span", required=True)
    ap.add_argument("--no-reference", action="store_true")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--manifest", default=None)
    args = ap.parse_args()
    args.trace = 1
    root = os.getcwd()
    bench = _load(os.path.join(root, "benchmark", "run.py"), "bench_run")
    bench.shutil = types.SimpleNamespace(rmtree=lambda *a, **k: None)
    made = {}
    make_context = bench.make_context

    def keep_context(*a, **kw):
        made["ctx"] = make_context(*a, **kw)
        return made["ctx"]
    bench.make_context = keep_context
    load = bench.load_module

    def load_module(path):
        mod = load(path)
        if hasattr(mod, "Cell"):
            measure = mod.Cell.measure

            def keep_window(self, *a, **kw):
                made["window"] = measure(self, *a, **kw)
                return made["window"]
            mod.Cell.measure = keep_window
            if args.no_reference:
                mod.Cell.check = lambda self: {
                    "correct": False, "attempted": 0, "failed": 0,
                    "compared": {}}
        return mod
    bench.load_module = load_module
    result = bench.run_cell(args, root=root, require_chip=not args.cpu,
                            manifest_path=args.manifest)
    if args.no_reference:
        result["correct"] = None
    ctx = made["ctx"]
    out = {"workload": args.workload, "seed": args.seed}
    from tpuframe.obs import timeline

    ring = timeline.spans()
    gaps = _load(os.path.join(root, "benchmark", "layer_metrics",
                              "_device_gaps.py"), "pr39_gaps")
    got = result["metrics"]
    out["trace_idle"] = {k: v["value"] for k, v in got.items()
                         if k.startswith("device_idle_share")}
    out["ring_gap_untraced"] = {k: v["value"] for k, v in got.items()
                                if k.startswith("device_gap")}
    traced = ctx.spans.spans.get("traced")
    clocks = [s for s in ring if s.name == "clock"]
    if not clocks and hasattr(timeline, "mark_clock"):
        # the ring has dropped it; the pair holds still
        timeline.mark_clock()
        clocks = timeline.spans("clock")
    out["ring_first_t1"] = ring[0].t1
    if any(s.name.startswith("device.") for s in ring):
        run = {"window": made["window"]}
        by = gap_by_span(gaps, run)
        if by is not None:
            out["untraced_gap_by_span_s"], out["untraced_s"] = by
        found = gaps.ring_window(run)
        if found is not None:
            _, t0, t1 = found
            out["untraced_window"] = [t0, t1]
            out["ring_records_per_s"] = sum(
                1 for s in ring if t0 <= s.t0 < t1) / (t1 - t0)
            out["ring_reaches_back_s"] = t0 - ring[0].t1
            for name in ("device.decode", "device.prefill", "device.step",
                         "engine.decode.dispatch", "engine.decode.fetch",
                         "engine.prefill.dispatch", "engine.prefill.fetch",
                         "sched.step"):
                ms = [x.ms for x in ring
                      if x.name == name and t0 <= x.t0 < t1]
                if ms:
                    out.setdefault("untraced_ms", {})[name] = _quant(ms)
    if traced and any(s.name.startswith("device.") for s in ring):
        lo, hi = traced[-1]
        run = {"window": {"kind": "serve", "opened_at": lo,
                          "wall_s": hi - lo}}
        out["traced_s"] = hi - lo
        out["ring_gap_traced"] = gaps.gap_share(run)
        out["ring_gap_traced_by_span_s"] = gap_by_span(gaps, run)[0]
        out.update(trace_readings(ctx.tracer.out_dir, ring,
                                  clocks[-1].args, args.span, lo, hi))
    shutil.rmtree(ctx.tracer.out_dir, ignore_errors=True)
    out["ring_len"] = len(ring)
    out["device_records"] = sum(s.name.startswith("device.") for s in ring)
    dest = os.path.join(root, "chiprun_out", "pr39")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, f"{args.workload}_{args.seed}.json"),
              "w") as f:
        json.dump({"validate": out, "result": result}, f, default=str)
    print(json.dumps(out, default=str), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
