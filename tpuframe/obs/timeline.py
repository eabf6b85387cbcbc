"""Profiling hooks — the HOROVOD_TIMELINE replacement (SURVEY.md §5.1).

Horovod records per-tensor negotiate/fuse/NCCL phases to a Chrome trace; on
TPU the equivalent visibility comes from the XLA/jax profiler: a perfetto/
TensorBoard trace of the compiled step, including the all-reduce ops and
their overlap with compute.  ``TPUFRAME_TRACE_DIR`` env or config triggers a
trace of steps [start, start+count) in the harness.

The host side of that picture is ``span()``: one primitive for every host
seam of the program (the loader's worker, the train loop, ``Scheduler``,
``LMEngine``).  A span is a ``jax.profiler.TraceAnnotation`` named
``tpuframe:<name>``, so a running profiler shows it on its caller's
thread, on one clock with the device ops; and it is a record in one
process-wide bounded ring on ``time.monotonic`` — the clock of
``Scheduler`` and of ``Request.arrival_t`` — that ``spans()``,
``durations_ms()`` and ``self_ms()`` read back, with or without a
profiler.  ``StepTimeline`` exports the ring as Chrome JSON.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import os
import threading
import time
from typing import NamedTuple

import jax


ENV_TRACE_STEPS = "TPUFRAME_TRACE_STEPS"
ENV_PROFILER_PORT = "TPUFRAME_PROFILER_PORT"


def parse_trace_steps(spec: str | None) -> tuple[int, int] | None:
    """Parse ``TPUFRAME_TRACE_STEPS="<start>:<count>"`` into
    ``(start, count)``.  Returns None for unset, malformed, or degenerate
    (count < 1, start < 0) specs — a bad knob must not kill the run."""
    if not spec or not spec.strip():
        return None
    parts = spec.strip().split(":")
    if len(parts) != 2:
        return None
    try:
        start, count = int(parts[0]), int(parts[1])
    except ValueError:
        return None
    if start < 0 or count < 1:
        return None
    return start, count


def start_profiler_server(port: int = 9012) -> bool:
    """On-demand profiling endpoint (TensorBoard 'capture profile')."""
    try:
        jax.profiler.start_server(port)
        return True
    except Exception:
        return False


@contextlib.contextmanager
def profile_trace(log_dir: str):
    """Trace a window of steps to ``log_dir`` (viewable in
    TensorBoard/perfetto; the analog of one Horovod timeline segment)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# The ring holds the last RING_SPANS spans of the process, always (no
# switch): ~300 bytes each, 20 MB when full.  Sized by the busiest
# producer, the serving loop of benchmark cell lm124m.serve_chat_r80: a
# scheduler step every 19.5 ms (51.3/s) closes 7 spans (sched.step, two
# sched.admit, sched.retire, engine.decode and its dispatch and fetch) and
# each of the 11.2 requests/s 5 more (sched.queue, engine.prefill and its
# dispatch and fetch, engine.insert): 51.3 * 7 + 11.2 * 5 = 415 spans/s.
# The readers run after the drain, so the measured window's first span has
# to outlive the window (20 s), the traced seconds (3) and the longest
# drain the runner allows (60): 83 s * 415 = 34.5k spans.  2**16 keeps
# 158 s of that traffic (the 20 s lead-in too); a training loop closes
# under 40 spans/s.
RING_SPANS = 1 << 16

ANNOTATION_PREFIX = "tpuframe:"


class Span(NamedTuple):
    """One closed host span.  ``t0``/``t1`` are ``time.monotonic``
    seconds; ``parent`` is the ``sid`` of the span that was open around
    it in the same thread (None at the top, and for ``record()``)."""

    name: str
    t0: float
    t1: float
    thread: str
    parent: int | None
    args: dict
    sid: int

    @property
    def ms(self) -> float:
        return 1e3 * (self.t1 - self.t0)


_ring: collections.deque = collections.deque(maxlen=RING_SPANS)
_ids = itertools.count(1)
_local = threading.local()


def _open_sids() -> list:
    try:
        return _local.open
    except AttributeError:
        _local.open = []
        return _local.open


class span:
    """``with span("loader.gather", batch=3): ...`` — a host span named
    ``name`` in the caller's thread, nested under the span open around
    it.  ``set(**args)`` adds arguments known only at the end (the
    scheduler's per-step counts)."""

    __slots__ = ("name", "args", "sid", "_parent", "_t0", "_annotation")

    def __init__(self, name: str, **args):
        self.name, self.args = name, args

    def __enter__(self) -> "span":
        open_sids = _open_sids()
        self._parent = open_sids[-1] if open_sids else None
        self.sid = next(_ids)
        open_sids.append(self.sid)
        self._annotation = jax.profiler.TraceAnnotation(
            ANNOTATION_PREFIX + self.name, **self.args)
        self._annotation.__enter__()
        self._t0 = time.monotonic()
        return self

    def set(self, **args) -> None:
        self.args.update(args)
        self._annotation.set_metadata(**args)

    def __exit__(self, *exc) -> bool:
        t1 = time.monotonic()
        self._annotation.__exit__(*exc)
        _local.open.pop()
        _local.closed = closed = Span(
            self.name, self._t0, t1, threading.current_thread().name,
            self._parent, self.args, self.sid)
        _ring.append(closed)
        return False


def record(name: str, t0: float, t1: float, **args) -> None:
    """Add an interval whose ends were read elsewhere on
    ``time.monotonic`` (a request's queue wait: due to admitted).  It
    nests under nothing: it may start long before any open span."""
    _ring.append(Span(name, t0, t1, threading.current_thread().name,
                      None, args, next(_ids)))


def last(name: str) -> Span | None:
    """The span this thread closed last, if its name is ``name``: how a
    caller reads the duration of the span its callee just made."""
    closed = getattr(_local, "closed", None)
    return closed if closed is not None and closed.name == name else None


def spans(name: str | None = None, t0: float | None = None,
          t1: float | None = None) -> list[Span]:
    """The ring's spans called ``name`` (all, if None) that started in
    ``[t0, t1)``, in the order they closed."""
    return [s for s in tuple(_ring)
            if (name is None or s.name == name)
            and (t0 is None or s.t0 >= t0) and (t1 is None or s.t0 < t1)]


def durations_ms(name: str, t0: float | None = None,
                 t1: float | None = None) -> list[float]:
    return [s.ms for s in spans(name, t0, t1)]


def self_ms(name: str, t0: float | None = None,
            t1: float | None = None) -> list[float]:
    """Each such span's duration less what the spans nested directly
    under it cover (they run one after another in its thread)."""
    covered: dict[int, float] = collections.defaultdict(float)
    for s in tuple(_ring):
        if s.parent is not None:
            covered[s.parent] += s.ms
    return [s.ms - covered[s.sid] for s in spans(name, t0, t1)]


class StepTimeline:
    """Host-side chrome-trace timeline — the direct HOROVOD_TIMELINE analog.

    Horovod's timeline shows per-tensor collective phases; under one-program
    SPMD the interesting host phases are coarser: data wait (input pipeline),
    step submit/execute, eval, checkpoint.  ``close`` writes every span the
    ring still holds since this object was made — the harness's phases and
    the loader's worker alike, one ``tid`` per thread — as a Chrome
    ``chrome://tracing`` / Perfetto JSON array.

    Enable via ``TPUFRAME_TIMELINE=/path/trace.json`` (env parity with
    ``HOROVOD_TIMELINE=file.json``) — the harness wires the phases.
    """

    def __init__(self, path: str):
        # On a multi-host slice with a shared filesystem, every process
        # writing the same path would clobber each other's full-file dump;
        # suffix with the process index so each host's timeline survives.
        if jax.process_count() > 1:
            root, ext = os.path.splitext(path)
            path = f"{root}.proc{jax.process_index()}{ext or '.json'}"
        self.path = path
        self._t0 = time.monotonic()

    @classmethod
    def from_env(cls) -> "StepTimeline | None":
        path = os.environ.get("TPUFRAME_TIMELINE")
        return cls(path) if path else None

    def phase(self, name: str, **args) -> span:
        return span(name, **args)

    def instant(self, name: str, **args) -> None:
        now = time.monotonic()
        record(name, now, now, **args)

    def close(self) -> None:
        import json

        pid, tids, events = jax.process_index(), {}, []
        for s in sorted(spans(t0=self._t0), key=lambda s: s.t0):
            ev = {"name": s.name, "ts": (s.t0 - self._t0) * 1e6,
                  "pid": pid, "tid": tids.setdefault(s.thread, len(tids)),
                  **({"args": s.args} if s.args else {})}
            if s.t1 == s.t0:
                ev.update(ph="i", s="p")
            else:
                ev.update(ph="X", dur=(s.t1 - s.t0) * 1e6)
            events.append(ev)
        with open(self.path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms",
                       "threadNames": {str(v): k for k, v in tids.items()}},
                      f)
