"""trace_reduce.py on hand-made events (exact arithmetic) and on a small
trace recorded on the v5e (``data/*.xplane.pb``)."""

import glob
import os

import pytest

from conftest import BENCH, HERE


@pytest.fixture(scope="module")
def tr(bench):
    return bench.load_module(os.path.join(BENCH, "trace_reduce.py"))


def op(lo, hi, hlo):
    import importlib

    tr = importlib.import_module("trace_reduce")
    return (lo, hi, *tr.parse_op(hlo))


def test_hand_made_events(tr):
    cats = tr.load_categories(os.path.join(BENCH, "op_categories.json"))
    dev = {"/device:TPU:0": [
        op(0.0, 1.0, "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8]{0} %copy.1), "
           "kind=kOutput, calls=%c"),
        op(1.0, 4.0, "%while.2 = (s32[]{:T(128)}) while((s32[]) %t), "
           "body=%b"),                               # holds the next two
        op(1.5, 2.0, "%attn.3 = (bf16[96,2048,64]{2,1,0:T(8,128)(2,1)}, "
           "f32[96,1,2048]{2,1,0}) custom-call(bf16[9]{0} %q), "
           "custom_call_target=\"tpu_custom_call\""),
        op(2.0, 3.0, "%dot.4 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %a, "
           "f32[8,8]{1,0} %all-reduce.9)"),
        op(6.0, 7.0, "%all-reduce.5 = f32[10]{0} all-reduce(f32[10]{0} %x)"),
    ]}
    host = [(3.9, 6.1, "data_wait"), (0.0, 0.5, "dispatch"),
            (3.0, 6.5, "outer_step")]    # nested spans: the innermost wins
    out = tr.reduce_events(dev, host, cats)
    assert out["window_s"] == pytest.approx(7.0)
    assert out["busy_s"] == pytest.approx(5.0)
    by = out["by_category_s"]
    assert by["conv_matmul_fusion"] == pytest.approx(2.0)
    assert by["flash_fwd"] == pytest.approx(0.5)
    assert by["collective"] == pytest.approx(1.0)
    assert by["control"] == pytest.approx(1.5)      # the while's own time
    assert sum(by.values()) == pytest.approx(out["busy_s"])
    assert out["idle_gaps"] == [["data_wait", pytest.approx(2.0)]]
    assert out["device_ops"][0] == ["conv_matmul_fusion:fusion",
                                    pytest.approx(1.0)] or \
        out["device_ops"][0][0] == "control:while"
    assert tr.reduce_events({}, host, cats) is None
    # with the runner's window span: the span itself is the window, idle
    # edges and all; an op that straddles an edge counts for its part
    inside = tr.reduce_events(dev, host + [(0.5, 5.0, "traced")], cats)
    assert inside["window_s"] == pytest.approx(4.5)
    assert inside["busy_s"] == pytest.approx(3.5)
    assert inside["by_category_s"]["conv_matmul_fusion"] == pytest.approx(1.5)
    assert "collective" not in inside["by_category_s"]
    assert inside["idle_gaps"] == [["data_wait", pytest.approx(1.0)]]


def test_two_devices_average(tr):
    cats = tr.load_categories(os.path.join(BENCH, "op_categories.json"))
    dev = {"/device:TPU:0": [op(0.0, 2.0, "%dot.1 = f32[8]{0} dot(f32[8] %a)")],
           "/device:TPU:1": [op(0.0, 1.0, "%dot.1 = f32[8]{0} dot(f32[8] %a)")]}
    out = tr.reduce_events(dev, [], cats)
    assert out["n_devices"] == 2 and out["busy_s"] == pytest.approx(1.5)
    assert out["window_s"] == pytest.approx(2.0)


def test_recorded_trace(tr):
    found = glob.glob(os.path.join(HERE, "data", "*.xplane.pb"))
    if not found:
        pytest.skip("no recorded trace beside the test")
    devices, host = tr.read_planes(found[0])
    assert devices, "the recorded trace holds no device operation"
    out = tr.reduce_events(devices, host, tr.load_categories(
        os.path.join(BENCH, "op_categories.json")))
    assert 0 < out["busy_s"] <= out["window_s"]
    assert sum(out["by_category_s"].values()) == pytest.approx(
        out["busy_s"], rel=1e-6)
    assert len(out["device_ops"]) <= 10
