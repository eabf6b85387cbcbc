"""Each runner end to end at toy size on the CPU (Pallas interpreting):
the result has the contract's keys, ``correct`` is true, nothing compiles
in the window, and no device metric is reported from a CPU."""

import contextlib
import io
import json

import pytest

from conftest import TOY, toy_args

DEVICE_METRICS = {"train_step_mfu.img", "train_step_mfu.seq",
                  "flash_attn_roofline", "serve_step_mfu",
                  "device_idle_share.img", "device_idle_share.seq",
                  "device_idle_share.serve"}


@pytest.mark.parametrize("workload,e2e", [
    ("resnet_toy.toy_train_b8", {"train_img_per_s_per_chip"}),
    ("lm_toy.toy_train_b4_s128", {"train_seq_per_s_per_chip"}),
    ("lm_toy.toy_serve", {"serve_ttft_p95_ms", "serve_tpot_p95_ms",
                          "serve_tok_per_s"}),
])
def test_runner_end_to_end(bench, workload, e2e):
    seconds = 3.0 if "serve" in workload else 1.0
    r = bench.run_cell(toy_args(workload, seed=2147483659, seconds=seconds),
                       require_chip=False, manifest_path=TOY)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"] and list(r)[-1] == "compared"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == e2e | {"setup_s"}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert r["lowerings_in_window"] == 0
    assert r["device"]["platform"] == "cpu"
    for pair in r["compared"].values():
        assert pair["value"] <= pair["limit"]


@pytest.mark.parametrize("workload", ["lm_toy.toy_train_b4_s128",
                                      "lm_toy.toy_serve"])
def test_traced_run_reports_no_device_metric_on_cpu(bench, workload):
    r = bench.run_cell(toy_args(workload, seconds=2.0, trace=1),
                       require_chip=False, manifest_path=TOY)
    assert r["correct"] is True
    assert r["metrics"] and not set(r["metrics"]) & DEVICE_METRICS
    assert "busy_s" not in r["device"] and "breakdown" not in r


def test_refuses_without_a_chip(bench):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        with pytest.raises(SystemExit) as e:
            bench.main(["--workload", "lm_toy.toy_serve", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], manifest_path=TOY)
    assert e.value.code == 2 and out.getvalue() == ""
    assert "no TPU" in err.getvalue()


def test_main_prints_the_result_last(bench, capsys):
    rc = bench.main(["--workload", "lm_toy.toy_serve", "--seed", "7",
                     "--seconds", "2", "--trace", "0"],
                    require_chip=False, manifest_path=TOY)
    captured = capsys.readouterr()
    assert rc == 0
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert last["correct"] is True
    tail = captured.err.strip().splitlines()[-len(last["compared"]):]
    assert all(line.startswith("compared ") and "limit" in line
               for line in tail)
