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

The device's side is ``device()``: for each training step the ring also
holds a ``device.<name>`` interval, on the same clock, on a lane
(``thread``) of its own called ``"device"``.  The device runs its queue
in order, so an interval starts at its launch or at the end of the one
before, whichever is later, and ends when a watcher thread's wait on one
of its outputs returns: on a v5e chip 1.0-3.3 ms after the step's last
program.  An interval says that a step was outstanding, not that the
device was busy: time the device idles inside it (a wait on an input
transfer, say) is not seen.  Time that no interval covers is time the
host's queue of steps had run dry.  The serving engine records none:
from the host, its launch reads about 1.1 ms after the device began and
its fetch about 2 ms after the device ended, which would count a fifth
of its window busy that the device spent idle.

A ``clock`` record (``mark_clock``; once a process, again whenever
``profile_trace`` starts) pairs ``time.monotonic_ns()`` with
``time.time_ns()``.  The profiler stamps ``TraceAnnotation``s and device
ops on ``time.time_ns``'s clock (``CLOCK_REALTIME``): an event of an
``.xplane.pb`` read through ``jax.profiler.ProfileData`` starts at the
``profile_start_time`` stat of its ``Task Environment`` plane plus its
``start_ns``, and a ring time ``t`` is ``1e9 * t + trace_ns -
monotonic_ns`` there.  On a v5e chip a ``tpuframe:engine.decode.fetch``
or ``tpuframe:train.dispatch`` annotation so placed starts 1.4-2.7 us
before its ring span's ``t0`` (the annotation opens first).
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import itertools
import os
import queue
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
    mark_clock()
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
# under 40 spans/s, one device.step interval a step among them.  At 3.9 ms
# steps the cell closes about 1,600 spans/s (222 steps and 10.8 requests
# a second on a v5e chip): 41 s of ring, which holds a traced run's
# untraced 12 s, its 3 traced seconds and the few seconds its drain steps
# (the profiler's write-out steps nothing), but not the longest drain.
RING_SPANS = 1 << 16

ANNOTATION_PREFIX = "tpuframe:"
DEVICE_THREAD = "device"   # the lane of the device.* intervals


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


def mark_clock() -> None:
    """A ``clock`` record: ``monotonic_ns`` (the ring's clock) beside
    ``trace_ns`` (the profiler's, ``time.time_ns``), read together, so
    that a ring export and a profiler trace can be overlaid."""
    mono_ns, trace_ns = time.monotonic_ns(), time.time_ns()
    record("clock", 1e-9 * mono_ns, 1e-9 * mono_ns, monotonic_ns=mono_ns,
           trace_ns=trace_ns)


class _DeviceLane:
    """The device's queue as the ring sees it: the end of the last
    ``device.*`` interval, and the watcher that waits on the outputs it
    was handed, in order.  The watcher only waits: it launches nothing and
    issues no collective."""

    def __init__(self):
        self._lock = threading.Lock()
        self._end = float("-inf")
        self._queue: queue.SimpleQueue | None = None
        self._thread: threading.Thread | None = None

    def _close(self, name: str, t_launch: float, t_ready: float,
               args: dict) -> None:
        with self._lock:
            t0 = max(t_launch, self._end)
            t1 = max(t_ready, t0)
            self._end = t1
            _ring.append(Span("device." + name, t0, t1, DEVICE_THREAD, None,
                              args, next(_ids)))

    def watch(self, name: str, t_launch: float, output, args: dict) -> None:
        with self._lock:
            if self._thread is None:
                self._queue = queue.SimpleQueue()
                self._thread = threading.Thread(
                    target=self._run, args=(self._queue,), daemon=True,
                    name="tpuframe-device-watch")
                self._thread.start()
            self._queue.put((name, t_launch, output, args))

    def _run(self, work: queue.SimpleQueue) -> None:
        try:
            for name, t_launch, output, args in iter(work.get, None):
                try:
                    output.block_until_ready()
                except Exception:  # noqa: BLE001 — its caller sees it
                    continue       # a failed step makes no interval
                self._close(name, t_launch, time.monotonic(), args)
        finally:
            # a watcher that ends for any cause lets the next watch() start
            # another, rather than fill a queue nobody drains
            with self._lock:
                if self._queue is work:
                    self._thread = self._queue = None

    def stop(self, timeout: float) -> None:
        with self._lock:
            thread, work = self._thread, self._queue
            self._thread = self._queue = None
        if thread is not None:
            work.put(None)
            thread.join(timeout)


_lane = _DeviceLane()


def stop_watcher(timeout: float = 1.0) -> None:
    """Let the watcher record what it was handed, then end its thread: a
    run's threads end with the run, and at exit before the backend goes.
    The next ``device()`` starts it again."""
    _lane.stop(timeout)


atexit.register(stop_watcher)
mark_clock()


def device(name: str, t_launch: float, output, **args) -> None:
    """Record the work launched at ``t_launch`` (``time.monotonic``, read
    when the executable's call returned) as a ``device.<name>`` interval on
    the ``"device"`` lane, which a watcher thread closes when ``output`` is
    ready: one result of the work that the next call does not donate.
    ``args`` are the record's; ``by`` names the launching thread."""
    args["by"] = threading.current_thread().name
    _lane.watch(name, t_launch, output, args)


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
    the loader's worker alike, one ``tid`` per thread, the ``device.*``
    intervals on a lane of their own — as a Chrome ``chrome://tracing`` /
    Perfetto JSON array.  Its ``clock`` key places the export on the
    profiler's clock: ``ts`` 0 is ``ts0_monotonic_ns``, and a profiler time
    is a monotonic one plus ``offset_ns``.

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
        clocks = spans("clock")
        if not clocks:            # the ring has outlived its clock record
            mark_clock()
            clocks = spans("clock")
        pair = clocks[-1].args
        with open(self.path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms",
                       "threadNames": {str(v): k for k, v in tids.items()},
                       "clock": {
                           "ts0_monotonic_ns": round(self._t0 * 1e9),
                           "offset_ns": pair["trace_ns"]
                           - pair["monotonic_ns"]}},
                      f)
