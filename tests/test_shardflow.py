"""Analysis v2+v3: collective-flow graph parser + structural detectors
+ the schedule/liveness plane.

Everything here runs without compiling anything: the golden fixtures
under ``tests/fixtures/hlo/`` are real optimized-HLO modules compiled
once on an 8-device CPU mesh (regenerate with
``tests/fixtures/regen_hlo.py``), and the seeded positives are
hand-written HLO snippets each detector must flag — every detector is
proven against both a known-bad program and every known-clean
strategy program.  The schedule plane (async start/done pairing,
overlap windows, liveness peaks) is additionally proven on seeded
*async* HLO, because CPU-compiled fixtures contain only sync
collectives.  The fused strategies sign ``declared_overlapped`` and so
run the exposed-comm detector as a LIVE gate, not report-only.
"""

import gzip
import json
import os
import types

import pytest

from tpuframe.analysis import hlo_audit, shardflow
from tpuframe.analysis import collective_graph as cg
from tpuframe.analysis.collective_graph import parse_graph

FIXDIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "fixtures", "hlo")

with open(os.path.join(FIXDIR, "goldens.json")) as _f:
    GOLDENS = json.load(_f)


def _fixture_text(name: str) -> str:
    entry = GOLDENS["strategies"][name]
    with gzip.open(os.path.join(FIXDIR, entry["file"]), "rt") as f:
        return f.read()


def _fake_audit(txt: str, *, name="seeded", ignore_below=0, meta=None):
    """The duck-typed slice of StrategyAudit the shardflow APIs read."""
    return types.SimpleNamespace(
        name=name, status="ok", reason="", violations=[],
        report=hlo_audit.parse_collectives(txt),
        budget=types.SimpleNamespace(ignore_below=ignore_below),
        compiled=types.SimpleNamespace(as_text=lambda: txt),
        meta=meta)


# ---------------------------------------------------------------------------
# Golden fixtures: parser shape pins + detectors clean on real programs.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(GOLDENS["strategies"]))
def test_golden_graph_shape(name):
    """Same fixture text => same parsed shape.  A parser change that
    drops computations/nodes/collectives fails here before it silently
    blinds the detectors."""
    graph = parse_graph(_fixture_text(name))
    assert graph.summary() == GOLDENS["strategies"][name]["summary"]
    assert graph.entry_computation is not None


@pytest.mark.parametrize("name", sorted(GOLDENS["strategies"]))
def test_golden_fixtures_pass_detectors(name):
    """Every registered strategy's real compiled program is clean under
    every structural detector (the acceptance criterion's clean half)."""
    entry = GOLDENS["strategies"][name]
    txt = _fixture_text(name)
    graph = parse_graph(txt)
    assert shardflow.detect_redundant_pairs(graph) == []
    assert shardflow.detect_wire_dtype(graph, entry["wire_dtype"]) == []
    assert shardflow.detect_replica_groups(
        graph, dict(tuple(p) for p in entry["mesh_shape"])) == []
    assert shardflow.census_cross_check(
        graph, hlo_audit.parse_collectives(txt)) == []


def test_goldens_match_checked_in_derived_budgets():
    """The fixtures, the derived-budget declarations, and the live gate
    all describe the same seven programs."""
    derived = shardflow.load_derived()
    assert derived is not None
    assert set(GOLDENS["strategies"]) == set(derived["strategies"])
    for name in GOLDENS["strategies"]:
        report = hlo_audit.parse_collectives(_fixture_text(name))
        decl = derived["strategies"][name]
        fresh = shardflow.derive_budget(report, decl["ignore_below"])
        assert fresh == decl, name


# ---------------------------------------------------------------------------
# Seeded positives: one known-bad program per detector.
# ---------------------------------------------------------------------------

_ADD = """\
%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%max (c: f32[], d: f32[]) -> f32[] {
  %c = f32[] parameter(0)
  %d = f32[] parameter(1)
  ROOT %m = f32[] maximum(%c, %d)
}
"""

_GROUPS8 = "replica_groups={{0,1,2,3,4,5,6,7}}"


def _module(entry_body: str) -> str:
    return (f"HloModule seeded\n\n{_ADD}\n"
            f"ENTRY %main (p0: f32[1024]) -> f32[1024] {{\n"
            f"{entry_body}\n}}\n")


def test_seeded_redundant_ag_rs_pair():
    txt = _module(
        f"  %p0 = f32[1024] parameter(0)\n"
        f"  %ag = f32[8192] all-gather(%p0), {_GROUPS8}, dimensions={{0}}\n"
        f"  %cp = f32[8192] copy(%ag)\n"
        f"  ROOT %rs = f32[1024] reduce-scatter(%cp), {_GROUPS8}, "
        f"to_apply=%add")
    findings = shardflow.detect_redundant_pairs(parse_graph(txt))
    assert len(findings) == 1
    assert "redundant pair" in findings[0]
    # the def-use chase went through the copy to the all-gather
    assert "%ag" in findings[0] and "%rs" in findings[0]


def test_seeded_redundant_pair_needs_same_groups():
    txt = _module(
        f"  %p0 = f32[1024] parameter(0)\n"
        f"  %ag = f32[8192] all-gather(%p0), {_GROUPS8}, dimensions={{0}}\n"
        f"  ROOT %rs = f32[1024] reduce-scatter(%ag), "
        f"replica_groups={{{{0,1,2,3}},{{4,5,6,7}}}}, to_apply=%add")
    assert shardflow.detect_redundant_pairs(parse_graph(txt)) == []


def test_seeded_duplicate_all_reduce():
    txt = _module(
        f"  %p0 = f32[1024] parameter(0)\n"
        f"  %ar1 = f32[1024] all-reduce(%p0), {_GROUPS8}, to_apply=%add\n"
        f"  %ar2 = f32[1024] all-reduce(%p0), {_GROUPS8}, to_apply=%add\n"
        f"  ROOT %o = f32[1024] add(%ar1, %ar2)")
    findings = shardflow.detect_redundant_pairs(parse_graph(txt))
    assert len(findings) == 1
    assert "duplicate all-reduce" in findings[0]
    assert "%ar1" in findings[0] and "%ar2" in findings[0]


def test_seeded_duplicate_ar_distinct_reduce_fns_clean():
    """A sum- and a max-reduction of one def are NOT duplicates."""
    txt = _module(
        f"  %p0 = f32[1024] parameter(0)\n"
        f"  %ar1 = f32[1024] all-reduce(%p0), {_GROUPS8}, to_apply=%add\n"
        f"  %ar2 = f32[1024] all-reduce(%p0), {_GROUPS8}, to_apply=%max\n"
        f"  ROOT %o = f32[1024] add(%ar1, %ar2)")
    assert shardflow.detect_redundant_pairs(parse_graph(txt)) == []


def test_seeded_wire_dtype_violation():
    txt = _module(
        f"  %p0 = f32[1024] parameter(0)\n"
        f"  ROOT %ar = f32[1024] all-reduce(%p0), {_GROUPS8}, "
        f"to_apply=%add")
    findings = shardflow.detect_wire_dtype(parse_graph(txt), "bf16")
    assert len(findings) == 1
    assert "carries f32" in findings[0]
    # ...but an f32 wire declaration, or a byte floor above the payload,
    # accepts the same program.
    assert shardflow.detect_wire_dtype(parse_graph(txt), "f32") == []
    assert shardflow.detect_wire_dtype(parse_graph(txt), "bf16",
                                       ignore_below=1 << 20) == []


def test_seeded_accidental_replication():
    txt = ("HloModule seeded\n\n"
           "ENTRY %main (p0: f32[1024,64]) -> f32[1024,64] {\n"
           "  %p0 = f32[1024,64] parameter(0)\n"
           "  ROOT %c = f32[1024,64] copy(%p0)\n}\n")
    declared = (("f32", (1024, 64), (128, 64)),)
    findings = shardflow.detect_replication(parse_graph(txt), declared)
    assert len(findings) == 1
    assert "accidental replication" in findings[0]
    # sharded as declared -> clean; tiny leaves stay under the floor
    sharded = ("HloModule ok\n\n"
               "ENTRY %main (p0: f32[128,64]) -> f32[128,64] {\n"
               "  %p0 = f32[128,64] parameter(0)\n"
               "  ROOT %c = f32[128,64] copy(%p0)\n}\n")
    assert shardflow.detect_replication(parse_graph(sharded),
                                        declared) == []
    assert shardflow.detect_replication(
        parse_graph(txt), declared, floor=1 << 30) == []


def test_seeded_replica_group_violations():
    mesh = {"data": 8}

    def groups_of(attr):
        txt = _module(
            f"  %p0 = f32[1024] parameter(0)\n"
            f"  ROOT %ar = f32[1024] all-reduce(%p0), "
            f"replica_groups={attr}, to_apply=%add")
        return shardflow.detect_replica_groups(parse_graph(txt), mesh)

    assert groups_of("{{0,1,2,3,4,5,6,7}}") == []
    unequal = groups_of("{{0,1,2},{3,4},{5,6,7}}")
    assert len(unequal) == 1 and "unequal group sizes" in unequal[0]
    overlap = groups_of("{{0,1},{1,2},{3,4},{5,6}}")
    assert len(overlap) == 1 and "overlap" in overlap[0]
    partial = groups_of("{{0,1},{2,3}}")
    assert len(partial) == 1 and "cover" in partial[0]


def test_seeded_replica_group_size_not_axis_product():
    # 12-device a×b mesh: size-2 groups partition the devices but no
    # combination of the declared axes (4, 3) explains a 2-wide group.
    mesh = {"a": 4, "b": 3}
    groups = "{" + ",".join(
        f"{{{2 * i},{2 * i + 1}}}" for i in range(6)) + "}"
    txt = _module(
        f"  %p0 = f32[1024] parameter(0)\n"
        f"  ROOT %ar = f32[1024] all-reduce(%p0), "
        f"replica_groups={groups}, to_apply=%add")
    findings = shardflow.detect_replica_groups(parse_graph(txt), mesh)
    assert len(findings) == 1
    assert "not a product of declared mesh axes" in findings[0]


def test_seeded_replica_group_iota_forms():
    mesh = {"data": 8}

    def iota_of(count, size):
        txt = _module(
            f"  %p0 = f32[1024] parameter(0)\n"
            f"  ROOT %ar = f32[1024] all-reduce(%p0), "
            f"replica_groups=[{count},{size}]<=[8], to_apply=%add")
        return shardflow.detect_replica_groups(parse_graph(txt), mesh)

    assert iota_of(1, 8) == []
    short = iota_of(2, 2)                 # covers 4 of 8 devices
    assert len(short) == 1 and "do not cover" in short[0]
    odd = iota_of(4, 2)                   # covers, but 2 not in {1, 8}
    assert len(odd) == 1 and "not a product" in odd[0]


def test_seeded_collective_permute_pairs():
    mesh = {"data": 8}

    def permute_of(pairs):
        txt = _module(
            f"  %p0 = f32[1024] parameter(0)\n"
            f"  ROOT %cp = f32[1024] collective-permute(%p0), "
            f"source_target_pairs={pairs}")
        return shardflow.detect_replica_groups(parse_graph(txt), mesh)

    assert permute_of("{{0,1},{1,2},{2,3}}") == []
    dup = permute_of("{{0,1},{0,2}}")
    assert len(dup) == 1 and "duplicate" in dup[0]
    out = permute_of("{{0,9}}")
    assert len(out) == 1 and "outside the declared" in out[0]


def test_census_cross_check_mismatch():
    """Feed the census a report for a DIFFERENT program — the cross
    check must notice the two parsers disagree."""
    txt = _module(
        f"  %p0 = f32[1024] parameter(0)\n"
        f"  ROOT %ar = f32[1024] all-reduce(%p0), {_GROUPS8}, "
        f"to_apply=%add")
    other = _module("  ROOT %p0 = f32[1024] parameter(0)")
    graph = parse_graph(txt)
    assert shardflow.census_cross_check(
        graph, hlo_audit.parse_collectives(txt)) == []
    findings = shardflow.census_cross_check(
        graph, hlo_audit.parse_collectives(other))
    assert len(findings) == 1 and "census mismatch" in findings[0]


# ---------------------------------------------------------------------------
# Derived budgets: drift in either direction fails; version skew skips.
# ---------------------------------------------------------------------------

_AR_TXT = None  # built once below


def _ar_audit():
    global _AR_TXT
    if _AR_TXT is None:
        _AR_TXT = _module(
            f"  %p0 = f32[1024] parameter(0)\n"
            f"  ROOT %ar = f32[1024] all-reduce(%p0), {_GROUPS8}, "
            f"to_apply=%add")
    return _fake_audit(_AR_TXT)


def _derived_file_for(audit) -> dict:
    return {
        "schema": shardflow.REPORT_SCHEMA,
        "jax": shardflow._jax_version(),
        "n_devices": 8,
        "strategies": {audit.name: shardflow.derive_budget(
            audit.report, audit.budget.ignore_below)},
    }


def test_budget_drift_clean_and_both_directions():
    audit = _ar_audit()
    derived = _derived_file_for(audit)
    assert shardflow.budget_drift(audit, derived) == []
    # declaration drifts above the program -> finding
    high = json.loads(json.dumps(derived))
    high["strategies"][audit.name]["kinds"]["all-reduce"]["bytes"] += 4
    assert any("drift on all-reduce" in p
               for p in shardflow.budget_drift(audit, high))
    # declaration misses a kind the program has -> finding too
    gone = json.loads(json.dumps(derived))
    del gone["strategies"][audit.name]["kinds"]["all-reduce"]
    assert any("drift on all-reduce" in p
               for p in shardflow.budget_drift(audit, gone))


def test_budget_drift_missing_entry_and_version_skew():
    audit = _ar_audit()
    derived = _derived_file_for(audit)
    nobody = json.loads(json.dumps(derived))
    nobody["strategies"] = {}
    assert any("no entry" in p
               for p in shardflow.budget_drift(audit, nobody))
    skew = json.loads(json.dumps(derived))
    skew["jax"] = "0.0.0-not-this-one"
    assert shardflow.budget_drift(audit, skew) == []
    assert shardflow.budget_drift(audit, None) != []


def test_derived_for_every_fixture_strategy():
    for name in GOLDENS["strategies"]:
        entry = shardflow.derived_for(name)
        assert entry is not None, name
        assert set(entry) == {"ignore_below", "kinds", "above_floor",
                              "total_bytes"}
        assert entry["total_bytes"] > 0


# ---------------------------------------------------------------------------
# The --json report schema + the compare contract (rc 0/1/2).
# ---------------------------------------------------------------------------

_TOP_KEYS = {"schema", "jax", "n_devices", "lint", "strategies"}
_STRATEGY_KEYS = {"name", "status", "reason", "violations", "collectives",
                  "total_bytes", "derived", "drift", "detectors", "graph",
                  "schedule", "schedule_drift", "overlap", "comm_split"}
_COMM_SPLIT_KEYS = {"slices", "ici", "dcn", "ici_bytes", "dcn_bytes",
                    "unattributed", "t_ici_ms", "t_dcn_ms", "generation"}
_DETECTOR_KEYS = {"redundant_pair", "wire_dtype", "replication",
                  "replica_groups", "census", "exposed_comm"}
_SCHEDULE_KEYS = {"ignore_below", "peak_live_bytes", "undonated_doubles",
                  "collectives", "async_pairs", "exposed_above_floor",
                  "interleavable_bytes"}
_OVERLAP_KEYS = {"generation", "comm_ms", "interleavable_ms",
                 "hideable_ms", "overlap_potential", "exposed",
                 "collectives_above_floor"}


def _schedule_file_for(audit) -> dict:
    graph = parse_graph(audit.compiled.as_text())
    return {
        "schema": shardflow.REPORT_SCHEMA,
        "jax": shardflow._jax_version(),
        "n_devices": 8,
        "strategies": {audit.name: shardflow.derive_schedule_entry(
            graph, ignore_below=audit.budget.ignore_below)},
    }


def _build_one_report(tmp_path, *, name="seeded"):
    audit = _fake_audit(_ar_audit().compiled.as_text(), name=name)
    derived_path = tmp_path / f"derived_{name}.json"
    derived_path.write_text(json.dumps(_derived_file_for(audit)))
    schedule_path = tmp_path / f"schedule_{name}.json"
    schedule_path.write_text(json.dumps(_schedule_file_for(audit)))
    finding = types.SimpleNamespace(rule="TF999", path="x.py", line=3,
                                    message="demo")
    return shardflow.build_report([audit], lint_findings=[finding],
                                  n_devices=8,
                                  derived_path=str(derived_path),
                                  schedule_path=str(schedule_path))


def test_report_schema_pinned(tmp_path):
    """The --json report shape is an API: obs-compare-style tooling
    parses it, so key changes must be deliberate (bump REPORT_SCHEMA)."""
    report = _build_one_report(tmp_path)
    assert set(report) == _TOP_KEYS
    assert report["schema"] == shardflow.REPORT_SCHEMA == 3
    assert report["lint"] == [{"rule": "TF999", "path": "x.py",
                               "line": 3, "message": "demo"}]
    (entry,) = report["strategies"]
    assert set(entry) == _STRATEGY_KEYS
    assert _STRATEGY_KEYS == set(shardflow.STRATEGY_REPORT_KEYS)
    assert set(entry["detectors"]) == _DETECTOR_KEYS
    assert set(entry["derived"]) == {"ignore_below", "kinds",
                                     "above_floor", "total_bytes"}
    assert set(entry["graph"]) == {"computations", "nodes",
                                   "entry_parameters",
                                   "collectives_by_kind"}
    assert set(entry["schedule"]) == _SCHEDULE_KEYS
    assert set(entry["overlap"]) == _OVERLAP_KEYS
    assert set(entry["comm_split"]) == _COMM_SPLIT_KEYS
    assert entry["drift"] == []
    assert entry["schedule_drift"] == []
    json.dumps(report)  # must be serializable as-is


def test_compare_reports_contract(tmp_path):
    base = _build_one_report(tmp_path)
    # identical reports: rc 0, one "ok" line per strategy
    rc, lines = shardflow.compare_reports(base, base)
    assert rc == 0 and any(ln.startswith("ok seeded") for ln in lines)
    # op-count change: rc 1 with a REGRESSION line
    worse = json.loads(json.dumps(base))
    worse["strategies"][0]["derived"]["kinds"]["all-reduce"]["count"] += 1
    rc, lines = shardflow.compare_reports(base, worse)
    assert rc == 1 and any("op count" in ln for ln in lines)
    # kind disappearing: rc 1
    gone = json.loads(json.dumps(base))
    del gone["strategies"][0]["derived"]["kinds"]["all-reduce"]
    rc, _ = shardflow.compare_reports(base, gone)
    assert rc == 1
    # byte move beyond tolerance: rc 1; within tolerance: rc 0
    fat = json.loads(json.dumps(base))
    kinds = fat["strategies"][0]["derived"]["kinds"]["all-reduce"]
    kinds["bytes"] = int(kinds["bytes"] * 1.5)
    rc, _ = shardflow.compare_reports(base, fat)
    assert rc == 1
    rc, _ = shardflow.compare_reports(base, fat, bytes_tol=0.6)
    assert rc == 0
    # a detector going from clean to firing: rc 1
    noisy = json.loads(json.dumps(base))
    noisy["strategies"][0]["detectors"]["wire_dtype"] = ["boom"]
    rc, lines = shardflow.compare_reports(base, noisy)
    assert rc == 1 and any("detector wire_dtype" in ln for ln in lines)
    # disjoint strategy sets: rc 2
    other = _build_one_report(tmp_path, name="different")
    rc, _ = shardflow.compare_reports(base, other)
    assert rc == 2


# ---------------------------------------------------------------------------
# Analysis v3: async pairing, overlap windows, liveness, schedule drift.
# ---------------------------------------------------------------------------

# A scheduled async module: the start->done pair is threaded through a
# copy AND a get-tuple-element (the chase the satellite fix targets),
# with an independent fusion scheduled inside the window.
_ASYNC_CHASED = """\
HloModule seeded_async, is_scheduled=true

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (p0: f32[1024], p1: f32[1024]) -> (f32[1024], f32[1024]) {
  %p0 = f32[1024]{0} parameter(0)
  %p1 = f32[1024]{0} parameter(1)
  %ags = f32[8192]{0} all-gather-start(f32[1024]{0} %p0), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %fus = f32[1024]{0} fusion(f32[1024]{0} %p1), kind=kLoop, calls=%add
  %cp = f32[8192]{0} copy(f32[8192]{0} %ags)
  %gte = f32[8192]{0} get-tuple-element(f32[8192]{0} %cp), index=0
  %agd = f32[8192]{0} all-gather-done(f32[8192]{0} %gte)
  %sl = f32[1024]{0} bitcast(f32[8192]{0} %agd)
  ROOT %out = (f32[1024]{0}, f32[1024]{0}) tuple(%sl, %fus)
}
"""


def test_async_pairing_chases_through_plumbing():
    """A -done reached only through copy/get-tuple-element chains still
    pairs with its -start (today's real schedulers thread exactly such
    plumbing between the two)."""
    comp = parse_graph(_ASYNC_CHASED).entry_computation
    pairs, problems = comp.pair_async()
    assert pairs == {"ags": "agd"}
    assert problems == []


def test_unpaired_async_start_fails_loudly():
    """Deleting the -done must produce a pairing problem — surfaced by
    the exposed-comm detector regardless of the overlap declaration."""
    torn = _ASYNC_CHASED.replace(
        "  %agd = f32[8192]{0} all-gather-done(f32[8192]{0} %gte)\n", ""
    ).replace("%sl = f32[1024]{0} bitcast(f32[8192]{0} %agd)",
              "%sl = f32[1024]{0} bitcast(f32[8192]{0} %gte)")
    graph = parse_graph(torn)
    _, problems = graph.entry_computation.pair_async()
    assert len(problems) == 1 and "unpaired async start" in problems[0]
    # the detector surfaces it even on an undeclared strategy
    assert any("unpaired async start" in f
               for f in shardflow.detect_exposed_comm(graph, False))


def test_overlap_window_contents_and_interleavable_set():
    comp = parse_graph(_ASYNC_CHASED).entry_computation
    view = cg.schedule_view(comp)
    (w,) = view.windows
    assert w.is_async and w.kind == "all-gather"
    assert w.done_name == "agd" and w.window_len == 4
    # the fusion is scheduled inside the window -> actually overlapped
    assert w.overlapped_compute == 1 and not w.exposed
    # ...and it is also the only compute op independent of the collective
    assert w.interleavable_compute == 1
    assert w.interleavable_bytes == 4096


def test_seeded_zero_overlap_positive():
    """The acceptance criterion's seeded zero-overlap HLO: flagged under
    a declared-overlapped strategy, report-only otherwise, and the gate
    refuses to run blind (seeded_schedule_positive is wired into
    check())."""
    graph = parse_graph(shardflow._SEEDED_EXPOSED_HLO)
    found = shardflow.detect_exposed_comm(graph, True)
    assert len(found) == 1 and "back-to-back" in found[0]
    assert shardflow.detect_exposed_comm(graph, False) == []
    # above a floor bigger than the payload, the declaration passes too
    assert shardflow.detect_exposed_comm(graph, True,
                                         ignore_below=1 << 20) == []
    assert shardflow.seeded_schedule_positive() == []
    # check() runs the seeded positives even with no audits at all
    monkey = shardflow._SEEDED_PEAK_BYTES
    try:
        shardflow._SEEDED_PEAK_BYTES = monkey + 1
        assert any("sweep is mis-measuring" in p
                   for p in shardflow.check([]))
    finally:
        shardflow._SEEDED_PEAK_BYTES = monkey


def test_liveness_peak_and_aliasing():
    """Hand-computable liveness: the sweep must count the async start's
    in-flight buffer and the escaping root, and alias ops own nothing."""
    graph = parse_graph(shardflow._SEEDED_EXPOSED_HLO)
    lv = cg.liveness(graph.entry_computation, graph.aliased_params)
    assert lv.peak_bytes == shardflow._SEEDED_PEAK_BYTES
    assert lv.total_defined_bytes > 0
    assert lv.undonated == ()


def test_liveness_undonated_doubling_flag():
    """An un-donated entry parameter whose exact shape recurs in the
    root output is the doubled-residency smell; donating it (the module
    header alias table) clears the flag."""
    body = """\
ENTRY %main (p0: f32[65536], p1: f32[16]) -> (f32[65536], f32[16]) {
  %p0 = f32[65536]{0} parameter(0)
  %p1 = f32[16]{0} parameter(1)
  %cp = f32[65536]{0} copy(f32[65536]{0} %p0)
  %cq = f32[16]{0} copy(f32[16]{0} %p1)
  ROOT %out = (f32[65536]{0}, f32[16]{0}) tuple(%cp, %cq)
}
"""
    undonated = parse_graph("HloModule m, is_scheduled=true\n\n" + body)
    lv = cg.liveness(undonated.entry_computation,
                     undonated.aliased_params, undonated_floor=1024)
    # p0 (256 KiB, shape-matches output 0) flags; p1 is under the floor
    assert lv.undonated == ("p0",)
    donated = parse_graph(
        "HloModule m, is_scheduled=true, input_output_alias={ {0}: (0, {},"
        " may-alias) }\n\n" + body)
    assert donated.aliased_params == frozenset({0})
    lv2 = cg.liveness(donated.entry_computation, donated.aliased_params,
                      undonated_floor=1024)
    assert lv2.undonated == ()


def test_seeded_liveness_drift_positive():
    """The acceptance criterion's seeded liveness drift: a tampered
    peak_live_bytes declaration must fail, version skew must skip, a
    missing entry/file must fail."""
    audit = _fake_audit(shardflow._SEEDED_EXPOSED_HLO, ignore_below=1024)
    sched = _schedule_file_for(audit)
    assert shardflow.schedule_drift(audit, sched) == []
    drifted = json.loads(json.dumps(sched))
    drifted["strategies"][audit.name]["peak_live_bytes"] += 4096
    probs = shardflow.schedule_drift(audit, drifted)
    assert len(probs) == 1 and "drift on peak_live_bytes" in probs[0]
    # drift the other direction fails identically
    lower = json.loads(json.dumps(sched))
    lower["strategies"][audit.name]["peak_live_bytes"] -= 4096
    assert shardflow.schedule_drift(audit, lower) != []
    # version skew: skip, not lie
    skew = json.loads(json.dumps(sched))
    skew["jax"] = "0.0.0-not-this-one"
    assert shardflow.schedule_drift(audit, skew) == []
    # missing entry / missing file: loud
    nobody = json.loads(json.dumps(sched))
    nobody["strategies"] = {}
    assert any("no entry" in p
               for p in shardflow.schedule_drift(audit, nobody))
    assert shardflow.schedule_drift(audit, None) != []


@pytest.mark.parametrize("name", sorted(GOLDENS["strategies"]))
def test_golden_fixtures_schedule_clean(name):
    """Every fixture passes the exposed-comm detector in report-only
    mode, and its async pairing has no problems."""
    graph = parse_graph(_fixture_text(name))
    assert shardflow.detect_exposed_comm(graph, False) == []
    for comp in graph.computations.values():
        _, problems = comp.pair_async()
        assert problems == []


_FUSED_FIXTURES = sorted(n for n in GOLDENS["strategies"] if "fused" in n)


@pytest.mark.parametrize("name", _FUSED_FIXTURES)
def test_fused_fixtures_pass_live_gate_with_interior_windows(name):
    """The fused strategies sign ``declared_overlapped=True``, which
    turns exposed-comm into a LIVE gate for them.  On the all-sync CPU
    fixture the declaration survives only because every gated window
    has legally interleavable interior compute — so assert both halves:
    the gate is clean AND the windows are provably non-empty.  A fusion
    regression that packs everything into one end-of-step bucket (no
    interior compute left) fails here."""
    entry = GOLDENS["strategies"][name]
    floor = entry["schedule"]["ignore_below"]
    graph = parse_graph(_fixture_text(name))
    assert shardflow.detect_exposed_comm(graph, True,
                                         ignore_below=floor) == []
    # nonzero-interior-window: the pinned schedule record agrees with a
    # fresh derivation, and both show real interleavable work.
    sched = entry["schedule"]
    assert sched["interleavable_bytes"] > 0, name
    assert sched["exposed_above_floor"] > 0, name  # sync CPU: exposed, hidden-able
    fresh = shardflow.derive_schedule_entry(graph, ignore_below=floor)
    assert fresh["interleavable_bytes"] == sched["interleavable_bytes"]
    # and at least one gated window individually carries interior compute
    windows = [w for comp in graph.computations.values()
               for w in cg.schedule_view(comp).windows
               if w.bytes >= floor]
    assert windows and all(w.interleavable_compute > 0 for w in windows)


def test_fused_fixture_set_is_complete():
    """Both signed strategies (dp and dp-zero1) regenerated into the
    goldens — a regen that silently drops one fails loudly here, not as
    a skipped parametrization."""
    assert _FUSED_FIXTURES == ["spec:dp=*+fused131072",
                               "spec:dp=*+zero1+fused131072"]


def test_fixtures_match_checked_in_derived_schedule():
    """The goldens' schedule records and derived_schedule.json are two
    spellings of one derivation — byte-equal, per strategy (the
    acceptance criterion's byte check)."""
    sched = shardflow.load_derived_schedule()
    assert sched is not None
    assert set(GOLDENS["strategies"]) == set(sched["strategies"])
    assert GOLDENS["jax"] == sched["jax"]
    for name, entry in GOLDENS["strategies"].items():
        assert entry["schedule"] == sched["strategies"][name], name
        # and both regenerate from the fixture text
        fresh = shardflow.derive_schedule_entry(
            parse_graph(_fixture_text(name)),
            ignore_below=entry["schedule"]["ignore_below"])
        assert fresh == entry["schedule"], name
        assert shardflow.schedule_for(name) == entry["schedule"]


def test_overlap_score_shape_and_bounds():
    for name in sorted(GOLDENS["strategies"]):
        graph = parse_graph(_fixture_text(name))
        report = hlo_audit.parse_collectives(_fixture_text(name))
        score = shardflow.overlap_score(
            graph, report, n_devices=8,
            ignore_below=GOLDENS["strategies"][name]["schedule"]
            ["ignore_below"])
        assert set(score) == _OVERLAP_KEYS
        assert 0.0 <= score["overlap_potential"] <= 1.0
        assert score["hideable_ms"] <= score["comm_ms"] + 1e-9
        # sync-only CPU programs: every above-floor collective exposed
        assert score["exposed"] == score["collectives_above_floor"]


def test_compare_schedule_section(tmp_path):
    """The 0/1/2 contract extended to the schedule plane: each metric
    regresses individually, and the section participates only when both
    reports carry it."""
    base = _build_one_report(tmp_path)
    # more exposed above-floor collectives: rc 1
    worse = json.loads(json.dumps(base))
    worse["strategies"][0]["schedule"]["exposed_above_floor"] += 1
    rc, lines = shardflow.compare_reports(base, worse)
    assert rc == 1 and any("exposed above-floor" in ln for ln in lines)
    # peak-live move beyond tolerance, either direction: rc 1
    for factor in (1.5, 0.5):
        fat = json.loads(json.dumps(base))
        sched = fat["strategies"][0]["schedule"]
        sched["peak_live_bytes"] = int(sched["peak_live_bytes"] * factor)
        rc, lines = shardflow.compare_reports(base, fat)
        assert rc == 1 and any("peak live bytes" in ln for ln in lines)
    # overlap-potential drop > 0.10: rc 1; a gain never regresses
    slow = json.loads(json.dumps(base))
    slow["strategies"][0]["overlap"]["overlap_potential"] -= 0.5
    rc, lines = shardflow.compare_reports(base, slow)
    assert rc == 1 and any("overlap potential" in ln for ln in lines)
    # schema-1 baseline without the schedule section still compares
    # clean on the structural metrics (participate-only-when-both)
    old = json.loads(json.dumps(base))
    for s in old["strategies"]:
        s.pop("schedule"), s.pop("overlap"), s.pop("schedule_drift")
    rc, _ = shardflow.compare_reports(old, worse)
    assert rc == 0
    rc, _ = shardflow.compare_reports(worse, old)
    assert rc == 0


def test_selfcheck_validates_golden_pair():
    """The checked-in docs/samples pair must keep the whole --compare
    contract alive, and the selfcheck must notice a broken pair."""
    assert shardflow.selfcheck() == []
    assert shardflow.selfcheck("/nonexistent-samples-dir") != []


def test_schedule_entry_is_integer_exact():
    """Every derived_schedule value is an int — the precondition for the
    byte-exact emit/regenerate contract."""
    sched = shardflow.load_derived_schedule()
    for name, entry in sched["strategies"].items():
        for key, value in entry.items():
            assert isinstance(value, int), (name, key, value)
