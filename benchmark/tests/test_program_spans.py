"""The readers of the program's own spans (PR 25), on the toy cells on the
CPU: a traced run reports every one of them as a finite number, next to
the runner-timed metric it should agree with.  The manifest is the toy one
plus the real manifest's new entries, pointed at the toy cells."""

import json
import math
import os

import pytest

from conftest import ROOT, TOY, toy_args

TOY_CELL = {"resnet50.train_b256": "resnet_toy.toy_train_b8",
            "lm124m.train_b8_s2048": "lm_toy.toy_train_b4_s128",
            "lm124m.serve_chat_r80": "lm_toy.toy_serve"}


def _load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    toy, real = _load(TOY), _load(os.path.join(ROOT, "BENCHMARK.json"))
    have = {m["name"] for m in toy["per_layer"]}
    added = []
    for m in real["per_layer"]:
        if m["source"] == "program_span" and m["name"] not in have:
            toy["per_layer"].append(dict(
                m, workloads=[TOY_CELL[c] for c in m["workloads"]]))
            added.append(m["name"])
    tmp = tmp_path_factory.mktemp("manifest")
    # the toy ResNet computes in float32 and its loader casts nothing: a
    # bfloat16 copy of it, as the real cell computes, has a loader.cast
    (resnet,) = [c for c in toy["configs"] if c["name"] == "resnet_toy"]
    conf = _load(os.path.join(ROOT, resnet["file"]))
    conf["dtype"] = conf["program"]["compute_dtype"] = "bfloat16"
    (tmp / "resnet_toy_bf16.json").write_text(json.dumps(conf))
    resnet["file"] = str(tmp / "resnet_toy_bf16.json")
    path = tmp / "BENCHMARK.json"
    path.write_text(json.dumps(toy))
    return str(path), added, toy


def test_the_real_manifest_brings_ten_new_span_metrics(manifest):
    _, added, _ = manifest
    assert len(added) == 10
    for name in added:
        quantity = name.rsplit(".", 1)[0] if name.endswith(
            (".img", ".seq")) else name
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", quantity + ".py")), name


@pytest.mark.parametrize("cell", sorted(TOY_CELL.values()))
def test_traced_toy_run_reports_every_span_metric(bench, manifest, cell):
    path, added, toy = manifest
    want = {m["name"] for m in toy["per_layer"]
            if m["name"] in added and cell in m["workloads"]}
    assert want
    r = bench.run_cell(toy_args(cell, seed=2147483659, seconds=3.0, trace=1),
                       require_chip=False, manifest_path=path)
    assert r["lowerings_in_window"] == 0 and r["failed"] == 0
    if "resnet" not in cell:   # the toy limits are float32's
        assert r["correct"] is True
    got = r["metrics"]
    assert want <= set(got), want - set(got)
    for name in want:
        v = got[name]["value"]
        assert math.isfinite(v) and v >= 0.0, (name, v)
    if "serve" in cell:
        # the inside and the outside time the same calls
        inside = (got["decode_dispatch_ms_p50"]["value"]
                  + got["decode_fetch_ms_p50"]["value"])
        assert inside <= got["decode_step_ms_p50"]["value"] + 0.05
        assert got["prefill_dispatch_ms_p50"]["value"] \
            <= got["prefill_ms_p50"]["value"]
        assert got["sched_queue_wait_p95_ms"]["value"] == pytest.approx(
            got["sched_queue_p95_ms"]["value"], abs=2.0)
    else:
        share = got[[n for n in want if "queue_full" in n][0]]["value"]
        assert share <= 100.0


def test_readers_find_nothing_in_a_program_without_the_ring(bench,
                                                            monkeypatch):
    """Laid over the parent commit, whose ``obs/timeline.py`` has no
    ring, each reader returns None and the result line leaves it out."""
    from tpuframe.obs import timeline

    monkeypatch.delattr(timeline, "self_ms")
    run = {"window": {"kind": "serve", "opened_at": 1.0, "wall_s": 2.0}}
    real = _load(os.path.join(ROOT, "BENCHMARK.json"))
    for m in real["per_layer"][-10:]:
        reader = bench.load_module(bench.find_reader(real, ROOT, m["name"]))
        assert reader.read(run) is None, m["name"]
