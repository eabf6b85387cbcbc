"""From the profiler's ``.xplane.pb`` to numbers: device busy and idle
time, time by op category, the ops that took most time, and the longest
idle gaps by what the host was doing.

Read with nothing but jax (``jax.profiler.ProfileData``).  The traced
window is the runner's host span ``bench:traced``, whole: idle time at its
edges counts (host and device clocks of one trace agree to a millisecond
or two).  Only a trace with no such span is read from its first to its
last device operation.  The profiler's own start-up, which stalls the
first traced step for seconds, and its write-out are outside the span: the
runner opens it after a settling step.  Busy is the union of the intervals
in which an operation ran on a device, clipped to the window and averaged
over the devices that ran anything.  An op's own time is its duration less that of the ops nested in
it (a ``while`` holds its body's ops), so categories add up to busy time.

Host spans are the runner's ``jax.profiler.TraceAnnotation``s whose names
start with ``bench:``; each idle gap of the first device goes to the span
that covers most of it.
"""

from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench:"
WINDOW_SPAN = "traced"


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_categories(path: str) -> list[tuple[str, re.Pattern]]:
    """The ordered rows of ``op_categories.json``."""
    with open(path) as f:
        rows = json.load(f)["categories"]
    return [(name, re.compile(pat)) for name, pat in rows]


def categorize(text: str, categories) -> str:
    for name, pat in categories:
        if pat.search(text):
            return name
    return "other"


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def _self_times(events: list[tuple[float, float, str]]) -> list[float]:
    """Own time of each ``(start, end, text)`` event of one line: its
    duration less its direct children's, with nesting read from the
    intervals."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [events[i][1] - events[i][0] for i in range(len(events))]
    stack: list[int] = []
    for i in order:
        lo, hi, _ = events[i]
        while stack and events[stack[-1]][1] <= lo:
            stack.pop()
        if stack and hi <= events[stack[-1]][1]:
            own[stack[-1]] -= hi - lo
        stack.append(i)
    return [max(x, 0.0) for x in own]


_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_EXTRA = re.compile(r"(custom_call_target=\"[^\"]*\"|kind=k\w+)")


def parse_op(text: str) -> tuple[str, str]:
    """``(short name, text searched for the category)`` of one device op.

    On the TPU an op event's name is its whole HLO line, ``%attn.41 =
    (bf16[96,2048,64]{...}, f32[96,1,2048]{...}) custom-call(operands...),
    custom_call_target="tpu_custom_call", ...``.  The short name is the
    instruction's without its number (``attn``); the searched text is the
    line up to its opcode with the layouts taken out, which holds the name
    and the result's shapes but no operand, then the call target and the
    fusion kind."""
    text = str(text)
    name, sep, rest = text.partition(" = ")
    short = re.sub(r"[.][0-9]+$", "", name.lstrip("%").strip())
    if not sep:
        return short, text
    rest = _LAYOUT.sub("", rest)
    m = _OPCODE.search(" " + rest)
    head = rest[: m.end() - 1] if m else rest[:200]
    extras = " ".join(_EXTRA.findall(rest))
    return short, f"%{short} = {head} {extras}".strip()


def read_planes(xplane_path: str):
    """``(device_events, host_spans)``: per device plane a list of
    ``(start_s, end_s, name, text)``; host spans ``(start_s, end_s, name)``
    without the prefix."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    devices: dict[str, list] = {}
    host_spans: list[tuple[float, float, str]] = []
    for plane in data.planes:
        pname = str(plane.name)
        if pname.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if str(line.name) != OPS_LINE:
                    continue
                evs = devices.setdefault(pname, [])
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    name, text = parse_op(ev.name)
                    evs.append((ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9,
                                name, text))
        elif pname.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = str(ev.name)
                    if name.startswith(HOST_SPAN_PREFIX):
                        host_spans.append((
                            ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9,
                            name[len(HOST_SPAN_PREFIX):]))
    devices = {k: v for k, v in devices.items() if v}
    return devices, host_spans


def reduce_events(devices: dict, host_spans: list, categories,
                  *, top: int = 10) -> dict | None:
    """The reduction proper, on plain tuples (what the recorded-trace test
    drives).  Returns None where no operation ran on a device."""
    traced = [(a, b) for a, b, name in host_spans if name == WINDOW_SPAN]
    if traced:
        lo, hi = traced[-1]
        # an op that straddles an edge counts for its part inside
        devices = {k: [(max(a, lo), min(b, hi), name, text)
                       for a, b, name, text in evs if b > lo and a < hi]
                   for k, evs in devices.items()}
        devices = {k: v for k, v in devices.items() if v}
        host_spans = [s for s in host_spans if s[2] != WINDOW_SPAN]
    if not devices:
        return None
    if not traced:
        lo = min(ev[0] for evs in devices.values() for ev in evs)
        hi = max(ev[1] for evs in devices.values() for ev in evs)
    window = hi - lo
    busy_per_device = []
    by_cat: dict[str, float] = {}
    by_cat_calls: dict[str, int] = {}
    by_op: dict[str, float] = {}
    first_gaps: list[tuple[float, float]] = []
    for idx, (_, evs) in enumerate(sorted(devices.items())):
        merged = _union([(a, b) for a, b, _, _ in evs])
        busy_per_device.append(sum(b - a for a, b in merged))
        own = _self_times([(a, b, t) for a, b, _, t in evs])
        for (a, b, name, text), s in zip(evs, own):
            cat = categorize(text, categories)
            by_cat[cat] = by_cat.get(cat, 0.0) + s
            by_cat_calls[cat] = by_cat_calls.get(cat, 0) + 1
            key = f"{cat}:{name}"
            by_op[key] = by_op.get(key, 0.0) + s
        if idx == 0:
            edges = [lo] + [t for iv in merged for t in iv] + [hi]
            first_gaps = [(edges[i], edges[i + 1])
                          for i in range(0, len(edges), 2)
                          if edges[i + 1] > edges[i]]
    n = len(busy_per_device)
    gaps_by_span: dict[str, float] = {}
    for a, b in first_gaps:
        # the span that covers most of the gap; of nested spans that
        # cover it alike, the innermost (shortest)
        best, best_key = "unannotated", (0.0, 0.0)
        for sa, sb, name in host_spans:
            key = (round(min(b, sb) - max(a, sa), 9), sa - sb)
            if key[0] > 0.0 and key > best_key:
                best, best_key = name, key
        gaps_by_span[best] = gaps_by_span.get(best, 0.0) + (b - a)
    scale = 1.0 / n  # seconds of one (average) device

    def ranked(d: dict, k: float = 1.0) -> list:
        return [[name, secs * k] for name, secs in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "n_devices": n,
        "window_s": window,
        "busy_s": sum(busy_per_device) / n,
        "by_category_s": {k: v * scale for k, v in by_cat.items()},
        "by_category_calls": by_cat_calls,
        "device_ops": ranked(by_op, scale),
        "idle_gaps": ranked(gaps_by_span),
    }


def reduce_trace(trace_dir: str, categories_path: str) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    devices, host_spans = read_planes(path)
    return reduce_events(devices, host_spans,
                         load_categories(categories_path))


def describe(xplane_path: str, limit: int = 6) -> str:
    """The planes, lines and a few events of a trace, for a first look by
    hand (`python benchmark/trace_reduce.py <dir>`)."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name} ({len(evs)} events)")
            for ev in evs[:limit]:
                try:
                    stats = {k: (v if not isinstance(v, (str, bytes))
                                 else str(v)[:120])
                             for k, v in dict(ev.stats).items()}
                except Exception:  # noqa: BLE001
                    stats = {}
                out.append(f"    {ev.name} start={ev.start_ns} "
                           f"dur={ev.duration_ns} {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    target = sys.argv[1]
    xp = target if target.endswith(".pb") else find_xplane(target)
    print(describe(xp))
    here = os.path.dirname(os.path.abspath(__file__))
    print(json.dumps(reduce_trace(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.dirname(xp)))),
        os.path.join(here, "op_categories.json")), indent=1))
