"""The host-span primitive of ``obs/timeline.py`` and the three seams that
use it: the loader's worker, ``Scheduler.step`` and the flash kernels'
names; the device's intervals (``device()``) and the ``clock`` record.
CPU only; a case reads the ring from the moment it starts, since
the ring is the process's and is never emptied."""

import json
import os
import sys
import threading
import time
import timeit

import numpy as np
import pytest

from tpuframe.obs import metrics, timeline
from tpuframe.obs.timeline import StepTimeline
from tpuframe.serve.replica import FakeEngine
from tpuframe.serve.scheduler import Request, Scheduler

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

def test_nesting_sets_parent_and_order_of_closing():
    t = time.monotonic()
    with timeline.span("t.outer", step=1) as outer:
        with timeline.span("t.inner"):
            pass
        with timeline.span("t.inner"):
            pass
    with timeline.span("t.outer"):
        pass
    got = timeline.spans(t0=t)
    assert [s.name for s in got] == ["t.inner", "t.inner", "t.outer",
                                     "t.outer"]
    first = got[2]
    assert first.sid == outer.sid and first.parent is None
    assert first.args == {"step": 1}
    assert [s.parent for s in got[:2]] == [first.sid, first.sid]
    assert got[3].parent is None           # the stack unwound
    assert all(s.thread == threading.current_thread().name for s in got)
    assert first.t0 <= got[0].t0 <= got[0].t1 <= got[1].t0 <= first.t1


def test_set_adds_args_known_at_the_end():
    t = time.monotonic()
    with timeline.span("t.set", a=1) as s:
        s.set(b=2)
    (got,) = timeline.spans("t.set", t0=t)
    assert got.args == {"a": 1, "b": 2}


def test_a_span_closes_and_unwinds_when_its_body_raises():
    t = time.monotonic()
    with pytest.raises(KeyError):
        with timeline.span("t.raises"):
            raise KeyError("x")
    with timeline.span("t.after"):
        pass
    assert timeline.spans("t.raises", t0=t)
    assert timeline.spans("t.after", t0=t)[0].parent is None


def test_a_second_thread_has_its_own_stack():
    t = time.monotonic()
    inside = threading.Event()
    go_on = threading.Event()

    def work():
        with timeline.span("t.thread.outer"):
            inside.set()
            assert go_on.wait(10)
            with timeline.span("t.thread.inner"):
                pass

    th = threading.Thread(target=work, name="t-second")
    with timeline.span("t.main"):
        th.start()
        assert inside.wait(10)
        with timeline.span("t.main.inner"):
            pass
        go_on.set()
        th.join(10)
        assert not th.is_alive()
    by = {s.name: s for s in timeline.spans(t0=t)}
    assert by["t.thread.outer"].thread == "t-second"
    assert by["t.thread.outer"].parent is None      # not under t.main
    assert by["t.thread.inner"].parent == by["t.thread.outer"].sid
    assert by["t.main.inner"].parent == by["t.main"].sid
    assert by["t.main"].thread == threading.current_thread().name


def test_record_takes_its_ends_from_the_caller_and_nests_under_nothing():
    t = time.monotonic()
    with timeline.span("t.around"):
        timeline.record("t.recorded", t - 5.0, t + 0.25, rid=7)
    (got,) = timeline.spans("t.recorded", t0=t - 6.0)
    assert (got.t0, got.t1, got.parent, got.args) == (
        t - 5.0, t + 0.25, None, {"rid": 7})
    assert got.ms == pytest.approx(5250.0)


def test_window_takes_spans_by_their_start():
    base = -1e6                        # no real span starts back there
    for i in range(4):
        timeline.record("t.window", base + i, base + i + 0.5)
    assert timeline.durations_ms("t.window", base + 1, base + 3) == [
        pytest.approx(500.0)] * 2
    assert len(timeline.spans("t.window", t0=base)) == 4
    assert len(timeline.spans("t.window", t1=base + 1)) == 1
    assert len(timeline.spans("t.window", t0=0.0)) == 0
    assert timeline.durations_ms("t.no_such_name") == []


def test_last_is_the_callees_span_only_right_after_it():
    def callee():
        with timeline.span("t.callee"):
            with timeline.span("t.callee.child"):
                pass

    callee()
    assert timeline.last("t.callee").name == "t.callee"
    assert timeline.last("t.other") is None
    with timeline.span("t.later"):
        pass
    assert timeline.last("t.callee") is None


def test_self_ms_is_duration_less_direct_children():
    t = time.monotonic()
    with timeline.span("t.self"):
        time.sleep(0.02)
        with timeline.span("t.self.child"):
            time.sleep(0.03)
            with timeline.span("t.self.grandchild"):
                time.sleep(0.01)
        with timeline.span("t.self.child"):
            time.sleep(0.01)
    (parent,) = timeline.spans("t.self", t0=t)
    children = timeline.durations_ms("t.self.child", t)
    (own,) = timeline.self_ms("t.self", t)
    assert own == pytest.approx(parent.ms - sum(children), abs=1e-6)
    assert 15.0 < own < parent.ms - 35.0
    # the grandchild is taken off the child, not off the parent twice
    assert timeline.self_ms("t.self.child", t)[0] == pytest.approx(
        children[0] - timeline.durations_ms("t.self.grandchild", t)[0],
        abs=1e-6)


def test_ring_is_bounded_and_keeps_the_newest(monkeypatch):
    import collections

    small = collections.deque(maxlen=8)
    monkeypatch.setattr(timeline, "_ring", small)
    for i in range(20):
        with timeline.span("t.bound", i=i):
            pass
    assert len(small) == 8
    assert [s.args["i"] for s in timeline.spans("t.bound")] == list(
        range(12, 20))


def test_ring_capacity_covers_the_serving_cell():
    """The arithmetic beside ``RING_SPANS``: the serving cell's spans per
    second, and the seconds its measured window has to outlive."""
    assert timeline._ring.maxlen == timeline.RING_SPANS
    per_step = len(("sched.step", "sched.admit", "sched.admit",
                    "sched.retire", "engine.decode",
                    "engine.decode.dispatch", "engine.decode.fetch"))
    per_request = len(("sched.queue", "engine.prefill",
                       "engine.prefill.dispatch", "engine.prefill.fetch",
                       "engine.insert"))
    per_s = per_step / 0.0195 + 11.2 * per_request
    assert per_s == pytest.approx(415, abs=1)   # the comment's figure
    window_s, traced_s, drain_s, lead_s = 20.0, 3.0, 60.0, 20.0
    must_keep = (window_s + traced_s + drain_s) * per_s
    assert must_keep < timeline.RING_SPANS / 1.5
    assert (lead_s + window_s + traced_s + drain_s) * per_s \
        < timeline.RING_SPANS


def test_scheduler_step_closes_the_counted_spans():
    """The per-step and per-request span counts the ring is sized by."""
    sched = Scheduler(FakeEngine(slots=2))
    sched.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=4))
    sched.step()
    t = time.monotonic()
    sched.step()                 # nothing admitted: the per-step spans
    names = sorted(s.name for s in timeline.spans(t0=t))
    assert names == ["sched.admit", "sched.admit", "sched.retire",
                     "sched.step"]  # + the real engine's three (below)


def test_span_costs_a_few_microseconds_with_the_profiler_off():
    def one():
        with timeline.span("t.cost", batch=1):
            pass

    n = 20000
    best = min(timeit.repeat(one, number=n, repeat=5)) / n
    print(f"\nspan() with the profiler off: {best * 1e6:.2f} us "
          f"(CPU microbenchmark, best of 5 x {n})", file=sys.stderr)
    assert best < 20e-6          # a few us; the bound leaves a busy host room


# ---------------------------------------------------------------------------
# StepTimeline, the ring's exporter
# ---------------------------------------------------------------------------

def test_step_timeline_exports_the_ring_as_chrome_json(tmp_path):
    with timeline.span("t.before_the_timeline"):
        pass
    tl = StepTimeline(str(tmp_path / "t.json"))

    def worker():
        with timeline.span("t.export.worker", batch=0):
            pass

    with timeline.span("t.export.step", step=1):
        with tl.phase("t.export.phase"):
            pass
    th = threading.Thread(target=worker, name="t-export-worker")
    th.start()
    th.join(10)
    tl.instant("t.export.instant", why="test")
    tl.close()
    doc = json.load(open(tl.path))
    evs = doc["traceEvents"]
    assert [e["name"] for e in evs] == [
        "t.export.step", "t.export.phase", "t.export.worker",
        "t.export.instant"]       # by start, nothing from before
    for e in evs:
        assert {"ph", "ts", "pid", "tid"} <= set(e) and e["ts"] >= 0
    assert [e["ph"] for e in evs] == ["X", "X", "X", "i"]
    assert evs[0]["args"] == {"step": 1} and evs[0]["dur"] >= evs[1]["dur"]
    assert evs[3]["args"] == {"why": "test"}
    assert evs[0]["tid"] == evs[1]["tid"] != evs[2]["tid"]
    assert doc["threadNames"][str(evs[2]["tid"])] == "t-export-worker"


def test_clock_record_pairs_the_ring_clock_with_the_profilers(tmp_path):
    if len(timeline._ring) < timeline.RING_SPANS:   # nothing dropped yet
        assert timeline._ring[0].name == "clock"    # the module's first
    t = time.monotonic()
    with timeline.profile_trace(str(tmp_path / "trace")):
        pass
    (made,) = timeline.spans("clock", t0=t)   # one at the trace's start
    assert set(made.args) == {"monotonic_ns", "trace_ns"}
    assert made.t0 == made.t1 == made.args["monotonic_ns"] * 1e-9
    offset = time.time_ns() - time.monotonic_ns()
    assert abs(made.args["trace_ns"] - made.args["monotonic_ns"]
               - offset) < 1e9
    tl = StepTimeline(str(tmp_path / "t.json"))
    tl.close()
    clock = json.load(open(tl.path))["clock"]
    newest = timeline.spans("clock")[-1].args
    assert clock == {"ts0_monotonic_ns": round(tl._t0 * 1e9),
                     "offset_ns": newest["trace_ns"] - newest["monotonic_ns"]}


# ---------------------------------------------------------------------------
# the device's intervals
# ---------------------------------------------------------------------------

def _wait_for(name: str, t: float, n: int) -> list:
    """The ``name`` records from ``t`` on, once the watcher has made
    ``n`` of them (10 s at most)."""
    deadline = time.monotonic() + 10.0
    while (len(timeline.spans(name, t0=t)) < n
           and time.monotonic() < deadline):
        time.sleep(0.005)
    return timeline.spans(name, t0=t)


class _Result:
    """An output of a fake asynchronous step: ready at ``ready_at``."""

    def __init__(self, ready_at: float):
        self.ready_at = ready_at

    def block_until_ready(self):
        time.sleep(max(self.ready_at - time.monotonic(), 0.0))
        return self


def test_async_step_makes_one_ordered_device_interval_a_call():
    from tpuframe.train import _HarnessStep

    queue_end = [time.monotonic()]

    def step(state, batch):
        """200 ms of device work a call, run in order after the last."""
        queue_end[0] = max(queue_end[0], time.monotonic()) + 0.2
        return state + 1, {"count": 3, "loss": _Result(queue_end[0])}

    wrapped = _HarnessStep(step)
    t = time.monotonic()
    state, ready = 0, []
    for i in range(5):
        state, metrics = wrapped(state, None)
        ready.append(metrics["loss"].ready_at)
        if i == 2:
            # the queue runs dry
            time.sleep(queue_end[0] - time.monotonic() + 0.2)
    got = _wait_for("device.step", t, 5)
    time.sleep(0.05)
    assert timeline.spans("device.step", t0=t) == got    # and no more
    dispatch = timeline.spans("train.dispatch", t0=t)
    assert [s.args["step"] for s in got] == [0, 1, 2, 3, 4]
    assert [s.args["step"] for s in dispatch] == [0, 1, 2, 3, 4]
    me = threading.current_thread().name
    for k, (d, s) in enumerate(zip(dispatch, got)):
        assert (s.thread, s.parent, s.args["by"]) == ("device", None, me)
        assert s.t0 >= d.t1 and s.t1 >= ready[k] and s.t1 >= s.t0
    for a, b in zip(got, got[1:]):
        assert b.t0 >= a.t1                  # in order, no overlap
    # queued behind the one before, it starts where that one ends; after
    # the queue ran dry, at its launch (the dispatch span's end)
    assert got[1].t0 == got[0].t1 and got[2].t0 == got[1].t1
    assert got[3].t0 == dispatch[3].t1 > got[2].t1


def _watchers() -> list:
    return [th for th in threading.enumerate()
            if th.name == "tpuframe-device-watch"]


def test_stop_watcher_records_what_it_was_handed_and_joins():
    t = time.monotonic()
    timeline.device("t.stop", t, _Result(t + 0.05))
    assert len(_watchers()) == 1
    timeline.stop_watcher(timeout=10.0)
    assert _watchers() == []
    (made,) = timeline.spans("device.t.stop", t0=t)
    assert made.t1 >= t + 0.05
    timeline.device("t.stop", time.monotonic(), _Result(0.0))
    assert len(_watchers()) == 1            # the next one starts it again
    assert len(_wait_for("device.t.stop", t, 2)) == 2
    timeline.stop_watcher()


class _Failed:
    def __init__(self, error: Exception):
        self.error = error

    def block_until_ready(self):
        raise self.error


@pytest.mark.parametrize("error", [
    RuntimeError("the step failed on the device"),
    ValueError("a result that cannot be waited on")])
def test_a_failed_output_makes_no_interval_and_the_watcher_goes_on(error):
    t = time.monotonic()
    timeline.device("t.failed", t, _Failed(error))
    timeline.device("t.next", time.monotonic(), _Result(0.0))
    assert len(_wait_for("device.t.next", t, 1)) == 1
    assert timeline.spans("device.t.failed", t0=t) == []
    assert len(_watchers()) == 1


def test_a_watcher_that_ended_is_replaced_by_the_next_device_call(
        monkeypatch):
    class _Fatal:
        def block_until_ready(self):
            raise SystemExit   # no Exception: it ends the watcher's thread

    monkeypatch.setattr(threading, "excepthook", lambda args: None)
    timeline.stop_watcher()
    timeline.device("t.fatal", time.monotonic(), _Fatal())
    deadline = time.monotonic() + 10.0
    while _watchers() and time.monotonic() < deadline:
        time.sleep(0.005)
    assert _watchers() == []
    t = time.monotonic()
    timeline.device("t.after", t, _Result(0.0))
    assert len(_wait_for("device.t.after", t, 1)) == 1
    timeline.stop_watcher()


def test_device_records_leave_sched_step_self_time_alone():
    # the device lane nests under no host span, so an interval recorded
    # while a step's spans are open changes no span's self time
    t = time.monotonic()
    for _ in range(3):
        with timeline.span("sched.step"):
            with timeline.span("sched.admit"):
                time.sleep(0.002)
            timeline.device("t.self", time.monotonic(), _Result(0.0))
            time.sleep(0.002)
    devs = _wait_for("device.t.self", t, 3)
    assert len(devs) == 3
    assert all((d.thread, d.parent) == ("device", None) for d in devs)
    steps = timeline.spans("sched.step", t0=t)
    admits = timeline.spans("sched.admit", t0=t)
    own = timeline.self_ms("sched.step", t)
    assert own == pytest.approx([s.ms - a.ms for s, a in zip(steps, admits)],
                                abs=1e-9)


# ---------------------------------------------------------------------------
# Scheduler over the fake engine, on an injected clock
# ---------------------------------------------------------------------------

class _Clock:
    """Advances 1 ms at every read: every interval is exact."""

    def __init__(self):
        self.t = 1000.0

    def __call__(self) -> float:
        self.t += 0.001
        return self.t


@pytest.fixture()
def drained():
    t = time.monotonic()
    clock = _Clock()
    sched = Scheduler(FakeEngine(slots=2, step_delay_s=0.002), clock=clock)
    for rid in range(5):
        sched.submit(Request(rid=100 + rid, prompt=[1, 2, 3],
                             max_new_tokens=3, arrival_t=999.0 + rid / 10))
    steps = 0
    while sched.has_work():
        sched.step()
        steps += 1
        assert steps < 50
    return sched, steps, t


def test_scheduler_sets_admit_t_between_arrival_and_first_token(drained):
    sched, _, _ = drained
    assert len(sched.completed) == 5
    for r in sched.completed:
        assert r.arrival_t < r.admit_t < r.first_token_t <= r.done_t


def test_sched_queue_plus_prefill_is_ttft(drained):
    sched, _, _ = drained
    # recorded on the scheduler's (here: the injected) clock
    queue = {s.args["rid"]: s for s in timeline.spans("sched.queue",
                                                      t0=990.0, t1=1010.0)
             if s.args["rid"] >= 100}
    assert sorted(queue) == [100, 101, 102, 103, 104]
    for r in sched.completed:
        q = queue[r.rid]
        assert (q.t0, q.t1) == (r.arrival_t, r.admit_t)
        prefill_ms = 1e3 * (r.first_token_t - r.admit_t)
        assert q.ms + prefill_ms == pytest.approx(r.ttft_ms(), abs=1e-6)


def test_sched_step_is_its_children_plus_self(drained):
    sched, steps, t = drained
    step_spans = timeline.spans("sched.step", t0=t)
    assert len(step_spans) == steps
    assert [s.args["step"] for s in step_spans] == list(range(1, steps + 1))
    assert sum(s.args["admitted"] for s in step_spans) == 5
    assert {"active", "produced", "queued"} <= set(step_spans[0].args)
    admits = timeline.spans("sched.admit", t0=t)
    retires = timeline.spans("sched.retire", t0=t)
    assert len(admits) == 2 * steps and len(retires) == steps
    assert sum(s.args["admitted"] for s in admits) == 5
    own = timeline.self_ms("sched.step", t)
    for s, self_ms in zip(step_spans, own):
        kids = [c for c in admits + retires if c.parent == s.sid]
        assert len(kids) == 3
        assert s.ms == pytest.approx(sum(c.ms for c in kids) + self_ms,
                                     abs=1e-6)
        # the fake engine's decode (a 2 ms sleep, no span) is the step's own
        assert self_ms >= 2.0
        assert all(s.t0 <= c.t0 and c.t1 <= s.t1 for c in kids)


def test_traced_request_reports_the_engines_prefill_span(tmp_path):
    """``engine_ms`` of the request's ``prefill`` trace event is the
    ``engine.prefill`` span's duration where the engine makes one."""
    from tpuframe.obs import events as obs_events

    class SpanningEngine(FakeEngine):
        def prefill(self, token_ids):
            with timeline.span("engine.prefill", tokens=len(token_ids)):
                return super().prefill(token_ids)

    obs_events.init(str(tmp_path))
    try:
        t = time.monotonic()
        for engine in (SpanningEngine(slots=1), FakeEngine(slots=1)):
            sched = Scheduler(engine)
            sched.submit(Request(rid=1, prompt=[1], max_new_tokens=1,
                                 arrival_t=time.monotonic(), trace="t1.0",
                                 span="s0"))
            sched.step()
    finally:
        obs_events.close()
    closes = [json.loads(line) for f in sorted(tmp_path.iterdir())
              for line in open(f) if '"span_close"' in line]
    engine_ms = [e.get("engine_ms") for e in closes if "engine_ms" in e]
    (made,) = timeline.spans("engine.prefill", t0=t)
    assert engine_ms == [pytest.approx(made.ms), None]


# ---------------------------------------------------------------------------
# ShardedLoader on a tiny data set
# ---------------------------------------------------------------------------

@pytest.fixture()
def epoch_read():
    from tpuframe.data import ShardedLoader
    from tpuframe.data.datasets import ArrayDataset

    ds = ArrayDataset({
        "image": np.arange(40 * 6, dtype=np.float32).reshape(40, 6),
        "label": np.arange(40, dtype=np.int32)})
    loader = ShardedLoader(ds, 8, None, shuffle=False, prefetch=2,
                           cast_floats="bfloat16")
    before = metrics.counters("loader.")
    t = time.monotonic()
    batches = list(loader.epoch(0))
    loader.close()
    after = metrics.counters("loader.")
    grew = {k: after[k] - before.get(k, 0) for k in after}
    return batches, t, grew


def test_loader_worker_spans_once_per_batch(epoch_read):
    batches, t, _ = epoch_read
    assert len(batches) == 5
    for name in ("loader.gather", "loader.cast", "loader.put",
                 "loader.queue_full"):
        got = timeline.spans(name, t0=t)
        assert [s.args["batch"] for s in got] == [0, 1, 2, 3, 4], name
        assert {s.thread for s in got} == {"tpuframe-prefetch"}, name
        assert all(s.parent is None for s in got), name
    # one after the other in the worker: gather, cast, put, queue_full
    for n in range(5):
        ends = [timeline.spans(name, t0=t)[n] for name in (
            "loader.gather", "loader.cast", "loader.put",
            "loader.queue_full")]
        assert all(a.t1 <= b.t0 for a, b in zip(ends, ends[1:]))


def test_loader_wait_is_the_consumers(epoch_read):
    batches, t, _ = epoch_read
    waits = timeline.spans("loader.wait", t0=t)
    assert len(waits) == len(batches) + 1       # and one for the sentinel
    assert {s.thread for s in waits} == {threading.current_thread().name}


def test_loader_counters_count_batches_and_bytes_put(epoch_read):
    batches, _, grew = epoch_read
    assert grew["loader.batches"] == 5
    put = sum(int(np.asarray(v).nbytes) for b in batches
              for v in b.values())
    assert put == 5 * (8 * 6 * 2 + 8 * 4)       # the image went as bfloat16
    assert grew["loader.bytes_put"] == put


def test_loader_without_a_cast_makes_no_cast_span():
    from tpuframe.data import ShardedLoader
    from tpuframe.data.datasets import ArrayDataset

    ds = ArrayDataset({"tokens": np.arange(64, dtype=np.int32)[:, None]})
    loader = ShardedLoader(ds, 8, None, prefetch=1)
    t = time.monotonic()
    stream = loader.epoch(0, skip=6)
    assert len(list(stream)) == 2
    loader.close()
    assert timeline.spans("loader.cast", t0=t) == []
    assert [s.args["batch"] for s in timeline.spans("loader.put", t0=t)] \
        == [6, 7]                               # numbered within the epoch


def test_cast_column_span_and_counter_once_a_loader():
    """Two epochs of one loader: the image column is cast once (not once
    an epoch, not once a batch), by the thread that built the loader; the
    worker's per-batch spans go on as before."""
    from tpuframe.data import ShardedLoader
    from tpuframe.data.datasets import ArrayDataset

    ds = ArrayDataset({
        "image": np.arange(40 * 6, dtype=np.float32).reshape(40, 6),
        "label": np.arange(40, dtype=np.int32)})
    before = metrics.counters("loader.").get("loader.bytes_cast_once", 0)
    t = time.monotonic()
    loader = ShardedLoader(ds, 8, None, prefetch=2, cast_floats="bfloat16")
    for epoch in (0, 1):
        assert len(list(loader.epoch(epoch))) == 5
    loader.close()
    (made,) = timeline.spans("loader.cast_column", t0=t)
    assert made.args == {"key": "image", "rows": 40, "bytes": 40 * 6 * 2}
    assert made.thread == threading.current_thread().name
    assert made.parent is None
    grew = metrics.counters("loader.")["loader.bytes_cast_once"] - before
    assert grew == made.args["bytes"]
    # before the first batch was gathered
    assert made.t1 <= timeline.spans("loader.gather", t0=t)[0].t0
    for name in ("loader.gather", "loader.cast", "loader.put",
                 "loader.queue_full"):
        got = timeline.spans(name, t0=t)
        assert [s.args["batch"] for s in got] == 2 * [0, 1, 2, 3, 4], name
        assert {s.thread for s in got} == {"tpuframe-prefetch"}, name
    for n in range(10):
        ends = [timeline.spans(name, t0=t)[n] for name in (
            "loader.gather", "loader.cast", "loader.put",
            "loader.queue_full")]
        assert all(a.t1 <= b.t0 for a, b in zip(ends, ends[1:]))


def test_loader_without_a_cast_makes_no_cast_column_span():
    from tpuframe.data import ShardedLoader
    from tpuframe.data.datasets import ArrayDataset

    ds = ArrayDataset({
        "image": np.arange(40 * 6, dtype=np.float32).reshape(40, 6)})
    before = metrics.counters("loader.").get("loader.bytes_cast_once", 0)
    t = time.monotonic()
    loader = ShardedLoader(ds, 8, None, prefetch=1)
    first = next(loader.epoch(0))
    loader.close()
    assert np.asarray(first["image"]).dtype == np.float32
    assert loader.dataset is ds
    assert timeline.spans("loader.cast_column", t0=t) == []
    assert timeline.spans("loader.cast", t0=t) == []
    assert metrics.counters("loader.").get(
        "loader.bytes_cast_once", 0) == before


def test_full_queue_shows_as_queue_full_time():
    """A consumer slower than the worker: the worker's time goes to
    ``loader.queue_full``, the consumer's ``loader.wait`` stays short."""
    from tpuframe.data import ShardedLoader
    from tpuframe.data.datasets import ArrayDataset

    ds = ArrayDataset({"x": np.arange(48, dtype=np.float32)[:, None]})
    loader = ShardedLoader(ds, 8, None, prefetch=1)
    t = time.monotonic()
    for _ in loader.epoch(0):
        time.sleep(0.03)
    loader.close()
    full = timeline.durations_ms("loader.queue_full", t)
    assert len(full) == 6 and sum(full) > 60.0
    assert sum(timeline.durations_ms("loader.wait", t)[1:]) < sum(full)


# ---------------------------------------------------------------------------
# the flash kernels' names in a trace
# ---------------------------------------------------------------------------

_FLASH_LINES = {
    "flash_fwd": '%flash_fwd.1 = (bf16[96,2048,64]{2,1,0:T(8,128)(2,1)}, '
                 'f32[96,1,2048]{2,1,0:T(1,128)S(1)}) custom-call(%bitcast.12,'
                 ' %bitcast.15, %bitcast.18), custom_call_target='
                 '"tpu_custom_call", operand_layout_constraints={bf16[96]{0}}',
    "flash_bwd_dkv": '%flash_bwd_dkv.1 = (bf16[96,2048,64]{2,1,0:T(8,128)'
                     '(2,1)}, bf16[96,2048,64]{2,1,0:T(8,128)(2,1)}) '
                     'custom-call(%bitcast.13, %bitcast.16), '
                     'custom_call_target="tpu_custom_call"',
    "flash_bwd_dq": '%flash_bwd_dq.1 = bf16[96,2048,64]{2,1,0:T(8,128)(2,1)}'
                    ' custom-call(%bitcast.14, %bitcast.17), '
                    'custom_call_target="tpu_custom_call"',
}


@pytest.mark.parametrize("category", sorted(_FLASH_LINES))
def test_renamed_flash_ops_keep_their_category(category):
    """``op_categories.json`` tells the three calls apart by result shapes
    and call target; the names they now carry change nothing.  The lines
    are the v5e compiler's for the LM cell's step (compile-only)."""
    sys.path.insert(0, _BENCH)
    try:
        import trace_reduce
    finally:
        sys.path.remove(_BENCH)
    cats = trace_reduce.load_categories(
        os.path.join(_BENCH, "op_categories.json"))
    short, text = trace_reduce.parse_op(_FLASH_LINES[category])
    assert short == category                # no longer "attn"
    assert trace_reduce.categorize(text, cats) == category
    old = _FLASH_LINES[category].replace("%" + category, "%attn")
    assert trace_reduce.categorize(trace_reduce.parse_op(old)[1],
                                   cats) == category


def test_flash_pallas_calls_carry_their_names():
    import jax
    import jax.numpy as jnp

    from tpuframe.ops.flash_attention import flash_mha

    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        return flash_mha(q, k, v, causal=True, interpret=True).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in text, name
