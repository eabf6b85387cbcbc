"""The readers of the device's gaps on a synthetic ring: known launches,
ready times and nested host spans, put in place of the program's ring.
Every number is worked out by hand below."""

import collections
import json
import os

import pytest

from conftest import ROOT

from tpuframe.obs import timeline
from tpuframe.obs.timeline import Span

M = "MainThread"
METRICS = ("device_gap_share.img", "device_gap_share.seq")


def _ring(rows):
    """``(name, t0, t1, thread, parent, args)`` rows, ``sid`` from 1."""
    ring = collections.deque(maxlen=timeline.RING_SPANS)
    for sid, (name, t0, t1, thread, parent, args) in enumerate(rows, 1):
        ring.append(Span(name, t0, t1, thread, parent, args, sid))
    return ring


def _dev(name, t0, t1, by=M):
    return (name, t0, t1, "device", None, {"by": by})


# A ring with two device lanes' names and host spans around them, in a
# window of [100, 101].  Host spans on the main thread, nested as
# indented (the rows below are in the order the spans close), count for
# nothing:
#   sched.step          100.00-100.40
#     sched.admit       100.00-100.05
#     engine.decode     100.05-100.30: dispatch 100.05-100.10,
#                                      fetch    100.10-100.30
#     sched.retire      100.30-100.33
#     sched.admit       100.33-100.40
#       engine.prefill  100.34-100.39: dispatch 100.34-100.36,
#                                      fetch    100.36-100.39
#   (the runner's loop, no program span, 100.40-100.45)
#   sched.step          100.45-101.20
#     sched.admit       100.45-100.47
#     engine.decode     100.47-100.90: dispatch 100.47-100.55,
#                                      fetch    100.55-100.90
#     sched.retire      100.90-100.95
#     sched.admit       100.95-101.20
# Device: decode 100.08-100.30, prefill 100.355-100.39, decode
# 100.53-100.90 (and one before the window).  Gaps: 100.000-100.080,
# 100.300-100.355, 100.390-100.530 and 100.900-101.000 = 0.375 s of 1.
SERVE_ROWS = [
    ("clock", 1.0, 1.0, M, None, {"monotonic_ns": 1, "trace_ns": 2}),
    _dev("device.decode", 99.0, 99.5),
    ("sched.admit", 100.00, 100.05, M, 1, {}),
    ("engine.decode.dispatch", 100.05, 100.10, M, 3, {}),
    ("engine.decode.fetch", 100.10, 100.30, M, 3, {}),
    _dev("device.decode", 100.08, 100.30),
    ("engine.decode", 100.05, 100.30, M, 1, {}),
    ("sched.retire", 100.30, 100.33, M, 1, {}),
    # a queue wait recorded on the same thread crosses the spans' edges
    ("sched.queue", 100.20, 100.34, M, None, {"rid": 3}),
    ("engine.prefill.dispatch", 100.34, 100.36, M, 8, {}),
    ("engine.prefill.fetch", 100.36, 100.39, M, 8, {}),
    _dev("device.prefill", 100.355, 100.39),
    ("engine.prefill", 100.34, 100.39, M, 7, {}),
    ("sched.admit", 100.33, 100.40, M, 1, {}),
    ("sched.step", 100.00, 100.40, M, None, {}),
    # another thread's span over every gap: not the launcher's
    ("loader.gather", 100.0, 101.0, "worker", None, {}),
    ("sched.admit", 100.45, 100.47, M, 11, {}),
    ("engine.decode.dispatch", 100.47, 100.55, M, 13, {}),
    ("engine.decode.fetch", 100.55, 100.90, M, 13, {}),
    _dev("device.decode", 100.53, 100.90),
    ("engine.decode", 100.47, 100.90, M, 11, {}),
    ("sched.retire", 100.90, 100.95, M, 11, {}),
    ("sched.admit", 100.95, 101.20, M, 11, {}),
    ("sched.step", 100.45, 101.20, M, None, {}),
]
SERVE_RUN = {"window": {"kind": "serve", "opened_at": 100.0, "wall_s": 1.0}}

# A training window from the first data_wait, 10.0, for 2 s; steps queue
# back to back but for one gap, 10.70-10.75: 2.5%.
TRAIN_ROWS = [
    ("clock", 1.0, 1.0, M, None, {"monotonic_ns": 1, "trace_ns": 2}),
    _dev("device.step", 9.5, 10.2),
    ("train.dispatch", 10.0, 10.01, M, None, {"step": 1}),
    _dev("device.step", 10.2, 10.7),
    _dev("device.step", 10.75, 11.5),
    _dev("device.step", 11.5, 12.3),
]
TRAIN_RUN = {"window": {"kind": "train", "wall_s": 2.0,
                        "spans": {"data_wait": [(10.0, 10.01)]}}}


@pytest.fixture()
def readers(bench):
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return {name: bench.load_module(bench.find_reader(manifest, ROOT, name))
            for name in METRICS}


def _read(monkeypatch, readers, rows, run):
    monkeypatch.setattr(timeline, "_ring", _ring(rows))
    return {name: r.read(run) for name, r in readers.items()}


def test_the_manifest_lists_both_with_their_cells(readers):
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    by = {m["name"]: m for m in manifest["per_layer"]}
    for name in METRICS:
        m = by[name]
        assert (m["source"], m["layer"], m["unit"], m["better"]) == (
            "program_span", "device", "%", "lower")
    assert by["device_gap_share.img"]["workloads"] == ["resnet50.train_b256"]
    assert by["device_gap_share.seq"]["workloads"] == [
        "lm124m.train_b8_s2048", "trinity_mini.train_b1_s8192",
        "kanana2_30b_a3b.train_b1_s8192"]


def test_training_ring_reads_by_hand(monkeypatch, readers):
    got = _read(monkeypatch, readers, TRAIN_ROWS, TRAIN_RUN)
    for name in METRICS:
        assert got[name] == pytest.approx(2.5, abs=1e-9)


def test_host_spans_count_for_nothing_beside_the_device_lane(monkeypatch):
    import _device_gaps

    monkeypatch.setattr(timeline, "_ring", _ring(SERVE_ROWS))
    assert _device_gaps.gap_share(SERVE_RUN) == pytest.approx(37.5,
                                                              abs=1e-9)


def test_a_ring_without_device_records_reads_nothing(monkeypatch, readers):
    import _device_gaps

    rows = [r for r in SERVE_ROWS if r[3] != "device"]
    monkeypatch.setattr(timeline, "_ring", _ring(rows))
    assert _device_gaps.gap_share(SERVE_RUN) is None
    rows = [r for r in TRAIN_ROWS if r[3] != "device"]
    assert all(v is None for v in _read(monkeypatch, readers, rows,
                                        TRAIN_RUN).values())


def test_a_ring_that_dropped_its_start_reads_what_it_kept(monkeypatch):
    import _device_gaps

    # the oldest record kept closed at 100.39: the window is 100.39-101,
    # with gaps 100.39-100.53 and 100.90-101.00
    rows = [r for r in SERVE_ROWS if r[2] >= 100.39]
    monkeypatch.setattr(timeline, "_ring", _ring(rows))
    assert _device_gaps.gap_share(SERVE_RUN) == pytest.approx(
        100.0 * 0.24 / 0.61, abs=1e-9)
