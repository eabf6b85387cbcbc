"""BENCHMARK.json against the contract's limits and against the files."""

import json
import os
import re

import pytest

from conftest import ROOT, TOY

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("path", [os.path.join(ROOT, "BENCHMARK.json"), TOY])
def test_manifest(path):
    m = _load(path)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    cells = {w["name"]: w for w in m["workloads"]}
    configs = {c["name"]: c for c in m["configs"]}
    assert len(cells) == len(m["workloads"]) <= 24
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        assert c["reduced"] == _load(os.path.join(ROOT, c["file"]))["reduced"]
        assert any(w["config"] == c["name"] for w in m["workloads"])
    pairs = set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = [os.path.join(ROOT, p, "traffic", w["traffic"] + ".json")
                   for p in m["paths"]]
        found = [t for t in traffic if os.path.isfile(t)]
        assert found, w["traffic"]
        t = _load(found[0])
        if "rate_metric" in t:   # the rate a training mix reports
            rate = [x for x in m["end_to_end"]
                    if x["name"] == t["rate_metric"]]
            assert rate and w["name"] in rate[0]["workloads"], w["name"]
        assert any(os.path.isfile(os.path.join(ROOT, p, "runners",
                                               t["runner"] + ".py"))
                   for p in m["paths"])
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(len(cells) // 4, 1)
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) == len(m["end_to_end"]) <= 16
    names = set()
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]), x["name"]
        assert UNIT.match(x["unit"]), x["unit"]
        assert x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES
        assert x["name"] not in names
        names.add(x["name"])
        for c in x.get("workloads", []):
            assert c in cells, (x["name"], c)
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert x["source"] in ("host_clock", "device_trace")
        assert 0 < x["bound"] <= 0.1

    def reports(metric, cell):
        if "workloads" in metric:
            return cell in metric["workloads"]
        # no list: an end-to-end metric is every cell's, a per-layer one is
        # reported wherever the metric it moves is
        return "moves" not in metric or reports(e2e[metric["moves"]], cell)

    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert x["moves"] in e2e and "\n" not in x["layer"]
        # <quantity>.<cells> is read by <quantity>.py unless it has its own
        readers = {x["name"], x["name"].rsplit(".", 1)[0]}
        assert any(os.path.isfile(os.path.join(ROOT, p, "layer_metrics",
                                               r + ".py"))
                   for p in m["paths"] for r in readers), x["name"]
        for cell in cells:
            if reports(x, cell):
                assert reports(e2e[x["moves"]], cell), (x["name"], cell)
    for cell in cells:
        assert any(reports(x, cell) and x["name"] != "setup_s"
                   for x in m["end_to_end"]), cell
        assert any(reports(x, cell) for x in m["per_layer"]), cell
    assert len(json.dumps(m)) < 64 * 1024
