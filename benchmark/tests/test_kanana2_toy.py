"""The ``kanana2_toy`` cell (``toy/BENCHMARK.kanana2.json``): the
deepseek_v3 model through ``runners/train.py`` on the CPU against
``reference/kanana2_30b_a3b.py``, under ``toy_train_b1_s64``'s job and
limits.  ``correct`` is true for the program, false with half of the
sequence left out of the loss, and false for the reference computed in
int8 in the program's place; a traced run reads the program's routing
counters and no device metric; the manifests keep the contract."""

import dataclasses
import json
import os

import numpy as np
import pytest

from conftest import BENCH, HERE, ROOT, toy_args

MANIFEST = os.path.join(HERE, "toy", "BENCHMARK.kanana2.json")
CELL = "kanana2_toy.toy_train_b1_s64"


def _run(bench, **kw):
    return bench.run_cell(toy_args(CELL, **kw), require_chip=False,
                          manifest_path=MANIFEST)


def _failed(result) -> list[str]:
    return [k for k, v in result["compared"].items()
            if not v["value"] <= v["limit"]]


def test_runner_end_to_end(bench):
    r = _run(bench, seed=2147483659)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"train_seq_per_s_per_chip", "setup_s"}
    assert r["lowerings_in_window"] == 0 and not _failed(r)


def test_traced_run_reads_the_counters_and_no_device_metric(bench):
    r = _run(bench, seconds=2.0, trace=1)
    assert r["correct"] is True
    assert 1.0 <= r["metrics"]["moe_expert_load_max_over_mean"]["value"] < 1.5
    assert not set(r["metrics"]) & {"train_step_mfu.mla",
                                    "flash_mla_roofline",
                                    "moe_experts_roofline",
                                    "device_idle_share.seq"}


def test_half_of_the_sequence_left_out(bench, monkeypatch):
    import tpuframe.train as train_mod

    orig = train_mod.build_harness

    def build(cfg):
        h = orig(cfg)

        def half(state, batch):   # the loss is over the first half only
            labels = np.array(batch["labels"])
            labels[:, labels.shape[1] // 2:] = -100
            return h.train_step(state, dict(batch, labels=labels))

        return dataclasses.replace(h, train_step=half)

    monkeypatch.setattr(train_mod, "build_harness", build)
    r = _run(bench)
    assert r["correct"] is False and _failed(r)


def test_control_in_int8_and_the_halved_reference_are_not_correct(bench):
    import jax.numpy as jnp

    toy = os.path.join(BENCH, "tests", "toy")
    with open(os.path.join(toy, "configs", "kanana2_toy.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(toy, "traffic", "toy_train_b1_s64.json")) as f:
        t = json.load(f)
    ref = bench.load_module(os.path.join(BENCH, "reference",
                                         "kanana2_30b_a3b.py"))
    runner = bench.load_module(os.path.join(BENCH, "runners", "train.py"))
    arch, job = cfg["arch"], t["job"]
    weights = ref.init_weights(arch, 11)
    ids = np.random.default_rng(11).integers(0, arch["vocab_size"],
                                             size=(3, 1, 65))
    batches = [{"input_ids": jnp.asarray(x[:, :-1], "int32"),
                "labels": jnp.asarray(x[:, 1:], "int32")} for x in ids]
    good = runner.reference_readings(ref, arch, job, weights, batches)
    for kw in ({"quant": "int8"}, {"keep_rows": 0}):
        low = runner.reference_readings(ref, arch, job, weights, batches,
                                        **kw)
        cmp = runner.compare(low, good, t["limits"])
        assert [k for k, v in cmp.items() if not v["value"] <= v["limit"]]
    same = runner.compare(good, good, t["limits"])
    assert all(v["value"] == 0 for v in same.values())


@pytest.mark.parametrize("path", [os.path.join(ROOT, "BENCHMARK.json"),
                                  MANIFEST])
def test_manifests_keep_the_contract(path):
    import test_manifest

    test_manifest.test_manifest(path)
