"""Static detectors over the collective-flow graph + derived budgets.

``hlo_audit`` polices *volume* (bytes per collective class against the
declared ceilings).  This module polices *structure*, on the typed graph
:mod:`tpuframe.analysis.collective_graph` builds from the same optimized
HLO:

  (a) :func:`detect_redundant_pairs` — an all-gather feeding a
      reduce-scatter of the same value over the same groups (the pair is
      a resharding no-op GSPMD should have cancelled), and duplicate
      all-reduces on one def (same operands, groups, and reduce fn —
      the sharding-annotation mistake that syncs a gradient twice).
  (b) :func:`detect_wire_dtype` — a floating collective wider than the
      strategy's declared wire dtype (an f32 gradient on a wire the
      strategy declares bf16 silently doubles every budget).
  (c) :func:`detect_replication` — a tensor the strategy declares
      sharded showing up among the entry parameters at its full
      (replicated) shape above a size floor: the accidental-replication
      failure GSPMD commits silently when one in_sharding is missing.
  (d) :func:`detect_replica_groups` — structural validity of every
      collective's replica groups against the strategy's declared mesh
      (equal sizes, disjoint, complete cover, group size a product of
      declared mesh axes) — the consistency check hierarchical
      ICI×DCN meshes (ROADMAP item 3, arXiv:2011.03641) will need
      per-slice.

From the same program the *exact* per-kind communication budget is
derived (:func:`derive_budget`, measured by ``hlo_audit``'s wire-traffic
ruler so derivation and ceiling audits never disagree) and diffed
against the checked-in declarations in ``derived_budgets.json`` —
drift in either direction fails the gate, and ``python -m
tpuframe.analysis --emit-budgets`` regenerates the file from one source
of truth.  ``budgets.py``'s hand-declared class ceilings stay as policy
(which *kinds* may exist at what order of magnitude); the derived file
is the byte-exact record of what the compiler actually emits today.

Analysis v3 adds the *schedule* plane on top of the structural one:

  (e) :func:`detect_exposed_comm` — async collective starts consumed
      back-to-back (zero overlap window).  Pairing failures (a start
      whose ``-done`` the chase cannot find) surface unconditionally;
      the zero-window finding itself only FAILS strategies that declare
      themselves overlapped (``StrategyMeta.declared_overlapped``) —
      CPU-compiled audit programs have no async scheduler, so today's
      strategies are reported exposed, not failed.
  (f) the per-strategy schedule/liveness record
      (:func:`derive_schedule_entry` — peak live bytes, un-donated
      doubled-residency inputs, window census) is pinned in
      ``derived_schedule.json`` under the exact ``--emit-budgets``
      contract: jax-version-stamped, drift in either direction fails,
      ``python -m tpuframe.analysis --emit-schedule`` regenerates it.
  (g) :func:`overlap_score` — hideable-comm milliseconds (roofline ICI
      model over each collective's wire bytes, capped by the HBM
      roofline over the compute legally interleavable with it) as a
      fraction of total comm: the ranked target list the bucketed-fusion
      work (ROADMAP item 4, arXiv:1802.05799) starts from, and the
      regression sentry it will be judged against.

Stdlib-only at import time (the ``hlo_audit`` contract); jax is touched
only inside the gate entry points that already run under the analysis
CLI's scrubbed child process.
"""

from __future__ import annotations

import itertools
import json
import os
import re
from collections import Counter

from tpuframe.analysis import collective_graph as cg
from tpuframe.analysis import hlo_audit

#: schema version of both the --json report and derived_budgets.json.
#: v2: per-strategy "schedule" (liveness/window census), "overlap"
#: (roofline overlap-potential score), and the exposed_comm detector.
#: v3: per-strategy "comm_split" — ICI vs DCN byte attribution from the
#: materialized replica groups against the declared hierarchical mesh.
REPORT_SCHEMA = 3

DERIVED_BUDGETS_PATH = os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "derived_budgets.json")

DERIVED_SCHEDULE_PATH = os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "derived_schedule.json")

#: golden --compare pair the jax-free selfcheck validates (pins both the
#: report schema and the schedule section of the differ).
SAMPLES_COMPARE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "..", "..", "docs", "samples", "analysis_compare"))

#: floating wire dtypes by width; integer/pred collectives are index
#: bookkeeping and never wire-dtype findings.
_FLOAT_WIDTHS = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2}

#: size floor for the replication detector — below this a replicated
#: tensor is a scalar/norm/metric, not the HBM-capacity failure class.
REPLICATION_FLOOR = 4096

# ---------------------------------------------------------------------------
# Detectors.  Each takes the graph (plus strategy facts) and returns
# finding strings; empty list == clean.
# ---------------------------------------------------------------------------


def _groups_key(node: cg.Node):
    if node.replica_groups is not None:
        return tuple(tuple(g) for g in node.replica_groups)
    return node.iota_groups


def detect_redundant_pairs(graph: cg.CollectiveGraph) -> list[str]:
    """(a) all-gather → reduce-scatter of one value over one group set,
    and duplicate all-reduces on one def."""
    findings: list[str] = []
    for comp in graph.computations.values():
        for node in comp.collectives():
            if node.kind != "reduce-scatter":
                continue
            for operand in node.operands:
                src_name = comp.resolve_value(operand)
                src = comp.nodes.get(src_name)
                if (src is not None and src.kind == "all-gather"
                        and _groups_key(src) == _groups_key(node)):
                    findings.append(
                        f"redundant pair in %{comp.name}: "
                        f"reduce-scatter %{node.name} consumes all-gather "
                        f"%{src.name} over the same replica groups — the "
                        f"gather/scatter round-trip is a no-op resharding "
                        f"({node.line})")
        by_def: dict[tuple, list[cg.Node]] = {}
        for node in comp.collectives():
            if node.kind != "all-reduce":
                continue
            roots = tuple(comp.resolve_value(o) for o in node.operands)
            reduce_fn = _reduce_fn(graph, node)
            by_def.setdefault((roots, _groups_key(node), reduce_fn),
                              []).append(node)
        for (roots, _, fn), nodes in sorted(by_def.items()):
            if len(nodes) > 1:
                names = ", ".join(f"%{n.name}" for n in nodes)
                findings.append(
                    f"duplicate all-reduce in %{comp.name}: {names} all "
                    f"{fn}-reduce the same def(s) "
                    f"{', '.join('%' + r for r in roots)} over the same "
                    f"groups — one collective's result should be reused")
    return findings


def _reduce_fn(graph: cg.CollectiveGraph, node: cg.Node) -> str:
    """Root opcode of the collective's to_apply computation ('add',
    'maximum', ...) — the semantic reduce fn, stable across the
    compiler's region-name suffixes."""
    for called in node.called:
        comp = graph.computations.get(called)
        if comp is not None and comp.root and comp.root in comp.nodes:
            return comp.nodes[comp.root].op
    return "?"


def detect_wire_dtype(graph: cg.CollectiveGraph, wire_dtype: str,
                      *, ignore_below: int = 0) -> list[str]:
    """(b) collectives carrying a float dtype wider than declared."""
    declared_w = _FLOAT_WIDTHS.get(wire_dtype)
    if declared_w is None:
        return [f"unknown declared wire dtype {wire_dtype!r} "
                f"(expected one of {sorted(_FLOAT_WIDTHS)})"]
    findings: list[str] = []
    for comp, node in graph.collectives():
        if node.result_bytes < ignore_below:
            continue
        wide = sorted(dt for dt in node.dtypes
                      if _FLOAT_WIDTHS.get(dt, 0) > declared_w)
        if not wide:
            continue
        findings.append(
            f"wire dtype in %{comp.name}: {node.kind} %{node.name} "
            f"carries {'/'.join(wide)} where the strategy declares "
            f"{wire_dtype} on the wire ({node.line})")
    return findings


def detect_replication(graph: cg.CollectiveGraph, declared_leaves,
                       *, floor: int = REPLICATION_FLOOR) -> list[str]:
    """(c) declared-sharded tensors appearing replicated at entry.

    ``declared_leaves``: iterable of ``(dtype, full_dims, shard_dims)``
    for every state leaf the strategy declares a sharding for (HLO dtype
    spelling, dim tuples).  A leaf whose per-device shape should differ
    from its full shape must NOT appear among the entry parameters at
    the full shape more often than other leaves legitimately land there.
    """
    entry = graph.entry_computation
    if entry is None or not declared_leaves:
        return []
    expected: Counter = Counter()
    for dt, _full, shard in declared_leaves:
        expected[(dt, tuple(shard))] += 1
    actual: Counter = Counter()
    for node in entry.parameters():
        if node.shapes:
            dt, dims = node.shapes[0]
            actual[(dt, tuple(dims))] += 1
    findings: list[str] = []
    flagged: set = set()
    for dt, full, shard in sorted(declared_leaves):
        full, shard = tuple(full), tuple(shard)
        if full == shard or (dt, full) in flagged:
            continue
        n = 1
        for d in full:
            n *= d
        if n * hlo_audit._DTYPE_BYTES.get(dt, 4) < floor:
            continue
        if actual.get((dt, full), 0) > expected.get((dt, full), 0):
            flagged.add((dt, full))
            findings.append(
                f"accidental replication: a {dt}[{','.join(map(str, full))}] "
                f"entry parameter sits at the FULL shape of a leaf this "
                f"strategy declares sharded to "
                f"[{','.join(map(str, shard))}] — one in_sharding is "
                f"missing or GSPMD dropped it")
    return findings


def detect_replica_groups(graph: cg.CollectiveGraph,
                          mesh_shape: dict) -> list[str]:
    """(d) structural validity of replica groups against the mesh."""
    if not mesh_shape:
        return []  # no declared mesh — nothing to check against
    sizes = [int(s) for s in mesh_shape.values()]
    n_devices = 1
    for s in sizes:
        n_devices *= s
    valid_sizes = set()
    for r in range(len(sizes) + 1):
        for combo in itertools.combinations(sizes, r):
            p = 1
            for s in combo:
                p *= s
            valid_sizes.add(p)
    findings: list[str] = []
    for comp, node in graph.collectives():
        where = f"{node.kind} %{node.name} in %{comp.name}"
        if node.kind == "collective-permute":
            pairs = node.source_target_pairs or ()
            srcs = [p[0] for p in pairs]
            dsts = [p[1] for p in pairs]
            if len(set(srcs)) != len(srcs) or len(set(dsts)) != len(dsts):
                findings.append(
                    f"replica groups: {where} has a duplicate "
                    f"source or target in source_target_pairs={pairs}")
            if any(d >= n_devices for p in pairs for d in p):
                findings.append(
                    f"replica groups: {where} names a device outside the "
                    f"declared {n_devices}-device mesh {mesh_shape}")
            continue
        if node.iota_groups is not None:
            count, size = node.iota_groups
            if count * size != n_devices:
                findings.append(
                    f"replica groups: {where} iota groups "
                    f"[{count},{size}] do not cover the declared "
                    f"{n_devices}-device mesh {mesh_shape}")
            elif size not in valid_sizes:
                findings.append(
                    f"replica groups: {where} group size {size} is not a "
                    f"product of declared mesh axes {mesh_shape}")
            continue
        groups = node.replica_groups
        if not groups:
            continue  # absent/empty groups = all devices, always valid
        flat = [d for g in groups for d in g]
        if len({len(g) for g in groups}) != 1:
            findings.append(
                f"replica groups: {where} has unequal group sizes "
                f"{[len(g) for g in groups]}")
            continue
        if len(set(flat)) != len(flat):
            findings.append(
                f"replica groups: {where} groups overlap (a device "
                f"appears twice): {groups}")
            continue
        if set(flat) != set(range(n_devices)):
            findings.append(
                f"replica groups: {where} groups cover {sorted(set(flat))}"
                f", not the declared {n_devices}-device mesh {mesh_shape}")
            continue
        if len(groups[0]) not in valid_sizes:
            findings.append(
                f"replica groups: {where} group size {len(groups[0])} is "
                f"not a product of declared mesh axes {mesh_shape} — the "
                f"collective spans a device set no mesh axis explains")
    return findings


def census_cross_check(graph: cg.CollectiveGraph,
                       report: hlo_audit.CollectiveReport) -> list[str]:
    """The two parsers must agree on the collective count per kind —
    a graph-parser regression must not silently blind the detectors."""
    g, r = graph.count_by_kind(), report.count_by_kind()
    if g == r:
        return []
    return [f"parser census mismatch: graph sees {g} but hlo_audit sees "
            f"{r} — collective_graph and hlo_audit disagree on what the "
            f"program contains"]


def detect_exposed_comm(graph: cg.CollectiveGraph,
                        declared_overlapped: bool,
                        *, ignore_below: int = 0) -> list[str]:
    """(e) exposed communication — a LIVE gate for declared-overlapped
    strategies, report-only for everyone else.

    Async pairing problems — a ``-start`` whose ``-done`` the chase
    cannot find — are findings REGARDLESS of the declaration: a blind
    window is a parser/schedule bug, not a policy choice.  For a
    declared-overlapped strategy the gate polices what the fusion pass
    CONTROLS, not what the backend chooses to lower:

    - an async start consumed back-to-back (zero-op window) always
      fails — the pass opened a window and wasted it;
    - a synchronous collective fails when the same program contains ANY
      async window — the backend demonstrably can split, so an unsplit
      collective is the pass's miss;
    - on an all-synchronous program (CPU XLA emits no async collective
      forms at all — PERF §21/§26) sync emission is not attributable to
      the pass, so it fails only when the window ALSO has zero legally
      interleavable compute: a declaration with nothing to hide behind
      is vacuously false.  Exposure still lands in the schedule record
      and the overlap score either way.

    Undeclared strategies only get the counts in the schedule record —
    never a gate failure."""
    findings: list[str] = []
    views = []
    for comp in graph.computations.values():
        view = cg.schedule_view(comp)
        findings.extend(view.problems)
        views.append((comp, view))
    if not declared_overlapped:
        return findings
    backend_splits = any(w.is_async for _, v in views for w in v.windows)
    for comp, view in views:
        for w in view.windows:
            if w.bytes < ignore_below or not w.exposed:
                continue
            if w.is_async:
                what = "consumed back-to-back (zero-op start->done window)"
            elif backend_splits:
                what = ("emitted synchronous (no start/done split) in a "
                        "program whose backend emits async forms")
            elif w.interleavable_compute == 0:
                what = ("emitted synchronous with ZERO legally "
                        "interleavable compute — nothing to overlap with")
            else:
                # Sync-only backend, interleavable compute present: the
                # declaration is honest about the program; exposure is
                # recorded and scored, not gated.
                continue
            findings.append(
                f"exposed communication in %{comp.name}: {w.kind} "
                f"%{w.name} ({w.bytes} B) is {what} but the strategy "
                f"declares its collectives overlapped — "
                f"{w.interleavable_compute} compute op(s) "
                f"({w.interleavable_bytes} B) were legally interleavable")
    return findings


# A minimal scheduled module whose async all-reduce is consumed
# back-to-back — zero ops inside the start->done window — while an
# independent fusion sits RIGHT THERE, legally interleavable.  The
# exposed-comm detector must flag it under a declared-overlapped
# strategy, and the liveness pass must reproduce its hand-computed peak.
_SEEDED_EXPOSED_HLO = """\
HloModule seeded_exposed_positive, is_scheduled=true

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[65536], p1: f32[65536]) -> (f32[65536], f32[65536]) {
  %p0 = f32[65536]{0} parameter(0)
  %p1 = f32[65536]{0} parameter(1)
  %ars = f32[65536]{0} all-reduce-start(f32[65536]{0} %p0), replica_groups={}, to_apply=%add
  %ard = f32[65536]{0} all-reduce-done(f32[65536]{0} %ars)
  %fus = f32[65536]{0} fusion(f32[65536]{0} %p1), kind=kLoop, calls=%add
  ROOT %out = (f32[65536]{0}, f32[65536]{0}) tuple(%ard, %fus)
}
"""

#: hand-computed liveness of ``_SEEDED_EXPOSED_HLO``'s entry: at the
#: all-reduce-start, its input p0 is still live alongside p1 and the
#: start's own 256 KiB result buffer (the done merely aliases it) — three
#: buffers; p0 then dies, and the fusion's result brings it back to three
#: (p1 + in-flight ars + fus, the latter two escaping through the root
#: tuple).  Peak is 3 x 262144 bytes.
_SEEDED_PEAK_BYTES = 3 * 262144


def seeded_schedule_positive() -> list[str]:
    """Self-test of the schedule plane — the gate refuses to run blind.

    Three invariants over the seeded zero-overlap program: the
    exposed-comm detector must flag it under a declared-overlapped
    strategy (and stay quiet under an undeclared one), the liveness
    estimator must reproduce the hand-computed peak, and the
    schedule-drift differ must catch a tampered peak declaration."""
    problems: list[str] = []
    graph = cg.parse_graph(_SEEDED_EXPOSED_HLO)
    found = detect_exposed_comm(graph, True)
    if len(found) != 1 or "back-to-back" not in found[0]:
        problems.append(
            f"seeded exposed-comm positive: expected exactly 1 zero-window "
            f"finding for a back-to-back all-reduce-start under a "
            f"declared-overlapped strategy, got {found!r} — the detector "
            f"is blind")
    if detect_exposed_comm(graph, False):
        problems.append(
            "seeded exposed-comm positive: an UNdeclared strategy must "
            "not fail on exposure (report-only contract broken)")
    entry = graph.entry_computation
    lv = cg.liveness(entry, graph.aliased_params)
    if lv.peak_bytes != _SEEDED_PEAK_BYTES:
        problems.append(
            f"seeded liveness positive: hand-computed peak "
            f"{_SEEDED_PEAK_BYTES} B but the estimator says "
            f"{lv.peak_bytes} B — the sweep is mis-measuring")
    fresh = derive_schedule_entry(graph, ignore_below=1024)
    tampered = dict(fresh, peak_live_bytes=fresh["peak_live_bytes"] + 4096)
    if not _schedule_entry_drift("seeded", fresh, tampered):
        problems.append(
            "seeded liveness-drift positive: a +4096 B tampered "
            "peak_live_bytes declaration produced no drift finding — "
            "the drift gate is blind")
    if _schedule_entry_drift("seeded", fresh, dict(fresh)):
        problems.append(
            "seeded liveness-drift positive: an identical declaration "
            "produced a drift finding — the differ is unstable")
    return problems


# ---------------------------------------------------------------------------
# Derived budgets: the exact per-kind record, emitted and drift-checked.
# ---------------------------------------------------------------------------


def derive_budget(report: hlo_audit.CollectiveReport,
                  ignore_below: int) -> dict:
    """Exact per-kind {bytes, count} of a program, measured by the same
    wire-traffic ruler as the ceiling audits (``hlo_audit``).

    ``kinds`` is the FULL census (no floor) — the drift gate pins every
    collective the compiler emits, not just the budget-relevant slice.
    ``above_floor`` is the slice the hand-declared ceiling actually
    polices (filtered at the budget's ``ignore_below``)."""
    counts = report.count_by_kind()
    above = report.filter(ignore_below)
    return {
        "ignore_below": int(ignore_below),
        "kinds": {k: {"bytes": int(b), "count": int(counts[k])}
                  for k, b in sorted(report.bytes_by_kind().items())},
        "above_floor": {k: int(b)
                        for k, b in sorted(above.bytes_by_kind().items())},
        "total_bytes": int(report.total_bytes),
    }


def elastic_transitions(n_devices: int = 8) -> tuple[tuple[int, int], ...]:
    """The membership transitions the gate pins: shrink to half the
    world and grow back — the 8→4→8 chaos tier's legs."""
    half = max(1, int(n_devices) // 2)
    return ((int(n_devices), half), (half, int(n_devices)))


def derive_resize(n_devices: int = 8) -> dict:
    """Exact shard-movement bytes of the elastic n→n′ resharding map —
    the resize priced like any other wire.

    Census: the flagship tiny-LM param tree the strategy audits compile
    (``strategies._lm_pieces``), under adamw's two flat moment vectors
    per leaf.  Movement comes from ``elastic.resharding``'s interval
    arithmetic over zero1's pad-to-multiple layout — pure shape math, no
    compile — so the pinned numbers are byte-exact and deterministic."""
    import jax
    import numpy as np

    from tpuframe.analysis import strategies
    from tpuframe.elastic import resharding

    _m, _l, _tx, (state, _b), _pb, _ab = strategies._lm_pieces()
    flat, _ = jax.tree_util.tree_flatten_with_path(state.params)
    leaves = [(jax.tree_util.keystr(path),
               int(np.prod(leaf.shape)) if leaf.shape else 1,
               np.dtype(leaf.dtype).itemsize)
              for path, leaf in flat]
    out = {}
    for n_from, n_to in elastic_transitions(n_devices):
        mv = resharding.resize_movement(leaves, n_from, n_to,
                                        moment_vectors=2)
        mv.pop("leaves")  # totals pin; per-leaf rows stay derivable
        out[f"{n_from}->{n_to}"] = mv
    return out


def resize_drift(derived_file: dict | None, *,
                 n_devices: int = 8) -> list[str]:
    """Diff the fresh resize derivation against the checked-in record —
    the same drift contract every collective budget lives under."""
    if derived_file is None:
        return []  # budget_drift already reports the missing file
    if derived_file.get("jax") != _jax_version():
        return []  # pinned to the emitting jax, like budget_drift
    declared = derived_file.get("elastic_resize")
    if declared is None:
        return ["elastic-resize budget missing from derived_budgets.json "
                "— run `python -m tpuframe.analysis --emit-budgets` to "
                "declare the resharding-map movement bytes"]
    fresh = derive_resize(n_devices)
    problems = []
    for key in sorted(set(fresh) | set(declared)):
        if fresh.get(key) != declared.get(key):
            problems.append(
                f"elastic-resize drift on {key}: derived "
                f"{fresh.get(key) or 'nothing'} but derived_budgets.json "
                f"declares {declared.get(key) or 'nothing'} — fix the "
                f"regression or re-emit with --emit-budgets")
    return problems


def load_derived(path: str = DERIVED_BUDGETS_PATH) -> dict | None:
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(data, dict) or "strategies" not in data:
        return None
    return data


def emit_derived(audits, *, n_devices: int, path: str =
                 DERIVED_BUDGETS_PATH) -> dict:
    """Regenerate ``derived_budgets.json`` from fresh audits — the
    one-source-of-truth half of the drift contract."""
    data = {
        "schema": REPORT_SCHEMA,
        "jax": _jax_version(),
        "n_devices": int(n_devices),
        "elastic_resize": derive_resize(n_devices),
        "strategies": {
            a.name: derive_budget(a.report, a.budget.ignore_below)
            for a in audits
            if a.status in ("ok", "violation") and a.report is not None
        },
    }
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return data


def budget_drift(audit, derived_file: dict | None) -> list[str]:
    """Diff a fresh derivation against the checked-in declaration.
    Either direction of drift is a finding; a strategy this jax can
    compile that has no declaration is one too."""
    if derived_file is None:
        return ["derived_budgets.json missing/unreadable — run "
                "`python -m tpuframe.analysis --emit-budgets`"]
    if derived_file.get("jax") != _jax_version():
        # Another jax emits different programs; the drift contract is
        # pinned to the version that emitted the file.  Not a finding —
        # the strategy audits still police the class ceilings here.
        return []
    declared = derived_file.get("strategies", {}).get(audit.name)
    if declared is None:
        return [f"[{audit.name}] compiles here but has no entry in "
                f"derived_budgets.json — run `python -m tpuframe.analysis "
                f"--emit-budgets` to declare its derived budget"]
    fresh = derive_budget(audit.report, audit.budget.ignore_below)
    problems = []
    for kind in sorted(set(fresh["kinds"]) | set(declared["kinds"])):
        f_e, d_e = fresh["kinds"].get(kind), declared["kinds"].get(kind)
        if f_e == d_e:
            continue
        problems.append(
            f"[{audit.name}] derived-budget drift on {kind}: compiled "
            f"program has {f_e or 'nothing'} but derived_budgets.json "
            f"declares {d_e or 'nothing'} — fix the regression or "
            f"re-emit with --emit-budgets")
    return problems


def derived_for(name: str, *, path: str = DERIVED_BUDGETS_PATH
                ) -> dict | None:
    """Checked-in derived budget for one strategy (tests assert against
    this instead of hand-copying byte constants)."""
    data = load_derived(path)
    if data is None:
        return None
    return data.get("strategies", {}).get(name)


# ---------------------------------------------------------------------------
# Derived schedule: liveness + window census, emitted and drift-checked
# (the --emit-budgets idiom, one file per plane).
# ---------------------------------------------------------------------------


def derive_schedule_entry(graph: cg.CollectiveGraph, *,
                          ignore_below: int) -> dict:
    """Integer-exact schedule/liveness record of one compiled program —
    what ``derived_schedule.json`` pins per strategy.

    ``peak_live_bytes``/``undonated_doubles`` come from the entry
    computation's liveness sweep (the floor for the donation flag is the
    budget's ``ignore_below`` — one ruler per strategy); the window
    census spans every computation, so collectives inside while bodies
    count.  All values are ints, so emission is byte-exactly
    reproducible."""
    entry = graph.entry_computation
    lv = (cg.liveness(entry, graph.aliased_params,
                      undonated_floor=max(int(ignore_below), 1))
          if entry is not None else None)
    n_coll = n_pairs = n_exposed = inter_bytes = 0
    for comp in graph.computations.values():
        pairs, _ = comp.pair_async()
        n_pairs += len(pairs)
        n_coll += len(comp.collectives())
        for w in cg.schedule_view(comp).windows:
            if w.bytes < ignore_below:
                continue
            if w.exposed:
                n_exposed += 1
            inter_bytes += w.interleavable_bytes
    return {
        "ignore_below": int(ignore_below),
        "peak_live_bytes": int(lv.peak_bytes) if lv else 0,
        "undonated_doubles": len(lv.undonated) if lv else 0,
        "collectives": int(n_coll),
        "async_pairs": int(n_pairs),
        "exposed_above_floor": int(n_exposed),
        "interleavable_bytes": int(inter_bytes),
    }


def load_derived_schedule(path: str = DERIVED_SCHEDULE_PATH
                          ) -> dict | None:
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(data, dict) or "strategies" not in data:
        return None
    return data


def emit_schedule(audits, *, n_devices: int,
                  path: str = DERIVED_SCHEDULE_PATH) -> dict:
    """Regenerate ``derived_schedule.json`` from fresh audits —
    ``python -m tpuframe.analysis --emit-schedule``."""
    data = {
        "schema": REPORT_SCHEMA,
        "jax": _jax_version(),
        "n_devices": int(n_devices),
        "strategies": {
            a.name: derive_schedule_entry(
                cg.parse_graph(a.compiled.as_text()),
                ignore_below=a.budget.ignore_below)
            for a in audits
            if a.status in ("ok", "violation") and a.compiled is not None
        },
    }
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return data


def _schedule_entry_drift(name: str, fresh: dict,
                          declared: dict) -> list[str]:
    """Field-by-field diff of one strategy's schedule record — either
    direction is a finding (a peak that *improved* silently is a stale
    declaration, same as a regression)."""
    problems = []
    for key in sorted(set(fresh) | set(declared)):
        if fresh.get(key) != declared.get(key):
            problems.append(
                f"[{name}] derived-schedule drift on {key}: compiled "
                f"program has {fresh.get(key)!r} but "
                f"derived_schedule.json declares {declared.get(key)!r} — "
                f"fix the regression or re-emit with --emit-schedule")
    return problems


def schedule_drift(audit, schedule_file: dict | None, *,
                   graph: cg.CollectiveGraph | None = None) -> list[str]:
    """Diff a fresh schedule derivation against the checked-in record —
    the budget_drift contract: missing file/entry is a finding, version
    skew is a skip (pinned to the emitting jax), drift either way
    fails."""
    if schedule_file is None:
        return ["derived_schedule.json missing/unreadable — run "
                "`python -m tpuframe.analysis --emit-schedule`"]
    if schedule_file.get("jax") != _jax_version():
        return []  # another jax schedules differently; pinned to emitter
    declared = schedule_file.get("strategies", {}).get(audit.name)
    if declared is None:
        return [f"[{audit.name}] compiles here but has no entry in "
                f"derived_schedule.json — run `python -m tpuframe."
                f"analysis --emit-schedule` to declare its schedule "
                f"record"]
    if graph is None:
        graph = cg.parse_graph(audit.compiled.as_text())
    fresh = derive_schedule_entry(graph,
                                  ignore_below=audit.budget.ignore_below)
    return _schedule_entry_drift(audit.name, fresh, declared)


def schedule_for(name: str, *, path: str = DERIVED_SCHEDULE_PATH
                 ) -> dict | None:
    """Checked-in schedule record for one strategy (tests assert against
    this instead of hand-copying byte constants)."""
    data = load_derived_schedule(path)
    if data is None:
        return None
    return data.get("strategies", {}).get(name)


def overlap_score(graph: cg.CollectiveGraph, report, *,
                  n_devices: int, ignore_below: int,
                  generation: str = "v5e") -> dict:
    """Overlap-potential score of one compiled program.

    Per above-floor collective window: its wire milliseconds come from
    the roofline ICI ring model over the bytes ``hlo_audit`` counted for
    that instruction (matched by source line, so the wire ruler — s8
    payloads, halved starts — carries over; result bytes are the
    fallback for ops the census floor dropped), and the compute
    *legally interleavable* with it is priced by the HBM roofline.  The
    hideable share of each window is ``min(comm, interleavable)``;
    ``overlap_potential`` is total hideable over total comm (1.0 when
    there is no above-floor comm — nothing to hide).  Floats, report
    plane only — the drift gate pins the integer schedule record, not
    this score."""
    from tpuframe.tune import roofline

    line_bytes: dict[str, list] = {}
    if report is not None:
        for op in report.ops:
            line_bytes.setdefault(op.line, []).append(int(op.bytes))
    comm = inter = hide = 0.0
    n_exposed = n_above = 0
    for comp in graph.computations.values():
        for w in cg.schedule_view(comp).windows:
            node = comp.nodes[w.name]
            matched = line_bytes.get(node.line)
            nbytes = matched.pop(0) if matched else w.bytes
            if nbytes < ignore_below:
                continue
            n_above += 1
            c_ms = roofline.comm_ms(generation, w.kind, nbytes, n_devices)
            i_ms = roofline.hbm_ms(generation, w.interleavable_bytes)
            comm += c_ms
            inter += i_ms
            hide += min(c_ms, i_ms)
            if w.exposed:
                n_exposed += 1
    return {
        "generation": generation,
        "comm_ms": round(comm, 6),
        "interleavable_ms": round(inter, 6),
        "hideable_ms": round(hide, 6),
        "overlap_potential": round(hide / comm, 4) if comm else 1.0,
        "exposed": int(n_exposed),
        "collectives_above_floor": int(n_above),
    }


def comm_split(graph: cg.CollectiveGraph, report, *, mesh_shape: dict,
               n_devices: int, generation: str = "v5e") -> dict:
    """ICI vs DCN byte attribution from replica groups.

    On a hierarchical mesh the ``slice`` axis is outermost, so logical
    device ``d`` lives in slice ``d // (n_devices / slices)`` — a
    collective whose materialized replica groups (or permute pairs)
    contain members of more than one slice must leave the ICI torus,
    and its FULL wire bytes are charged to DCN (conservative: the slow
    hop bounds the op).  Bytes use the census ruler (``hlo_audit`` op
    bytes matched by source line, like :func:`overlap_score`; result
    bytes as fallback), so every payload splits at its own dtype's width.
    Single-slice meshes attribute everything to ICI by construction.
    ``unattributed`` counts collectives whose iota group spec could not
    be materialized — those are charged to DCN, never dropped."""
    from tpuframe.tune import roofline

    # "slice" is mesh.SLICE_AXIS; spelled literally so the report stays
    # buildable without jax (mesh imports it).
    slices = int(mesh_shape.get("slice", 1)) if mesh_shape else 1
    if slices < 1 or n_devices % max(slices, 1):
        slices = 1
    inner = n_devices // slices
    line_bytes: dict[str, list] = {}
    if report is not None:
        for op in report.ops:
            line_bytes.setdefault(op.line, []).append(int(op.bytes))
    ici: dict[str, int] = {}
    dcn: dict[str, int] = {}
    unattributed = 0
    for _comp, node in graph.collectives():
        matched = line_bytes.get(node.line)
        nbytes = matched.pop(0) if matched else node.result_bytes
        crossing = False
        if slices > 1:
            if node.kind == "collective-permute":
                pairs = node.source_target_pairs or ()
                crossing = any(s // inner != t // inner
                               for s, t, *_ in pairs)
            else:
                groups = cg.materialized_groups(node, n_devices)
                if groups is None:
                    unattributed += 1
                    crossing = True
                else:
                    crossing = any(
                        len({d // inner for d in g}) > 1 for g in groups)
        bucket = dcn if crossing else ici
        bucket[node.kind] = bucket.get(node.kind, 0) + int(nbytes)
    ici_bytes = sum(ici.values())
    dcn_bytes = sum(dcn.values())
    return {
        "slices": slices,
        "ici": {k: int(v) for k, v in sorted(ici.items())},
        "dcn": {k: int(v) for k, v in sorted(dcn.items())},
        "ici_bytes": int(ici_bytes),
        "dcn_bytes": int(dcn_bytes),
        "unattributed": int(unattributed),
        "t_ici_ms": round(sum(
            roofline.comm_ms(generation, k, b, n_devices)
            for k, b in ici.items()), 6),
        "t_dcn_ms": round(sum(
            roofline.dcn_ms(generation, k, b, slices)
            for k, b in dcn.items()), 6),
        "generation": generation,
    }


#: one MegaScale DCN transfer: a host-transfer ``send`` whose payload is
#: the first tuple element and whose rendezvous tag names the collective
#: it carries, e.g. ``%send = (f32[1025,8,128]{...}, u32[], token[])
#: send(...), is_host_transfer=true, frontend_attributes={...
#: _xla_host_transfer_rendezvous="all-reduce.73_3"...}``.
_MEGASCALE_PAYLOAD_RE = re.compile(
    r"=\s*\((" + hlo_audit._DTYPE_RE + r")\[([0-9,]*)\]")
_MEGASCALE_KIND_RE = re.compile(
    r'_xla_host_transfer_rendezvous="([a-z\-]+)')


def megascale_split(hlo_text: str) -> dict:
    """Cross-slice (DCN) bytes the XLA:TPU backend moved through the
    MegaScale transport instead of plain collectives.

    On real multi-slice topologies the TPU compiler decomposes a
    slice-spanning collective itself: the in-slice legs stay HLO
    collectives (``comm_split`` attributes those) but the DCN hop is
    lowered to paired host-transfer ``send``/``recv`` custom channels
    tagged ``_xla_host_transfer_handler_name="xla_megascale_runtime"``
    — invisible to both the collective graph and ``hlo_audit``.  This
    counts each such send's payload bytes (at the payload dtype's own
    width) keyed by
    the collective kind its rendezvous tag names.  Returns
    ``{kind: bytes}``; empty for CPU-compiled or single-slice programs,
    so folding this into a ``comm_split`` DCN column is a no-op there.
    """
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if " send(" not in line or "is_host_transfer=true" not in line \
                or "xla_megascale_runtime" not in line:
            continue
        payload = _MEGASCALE_PAYLOAD_RE.search(line)
        kind = _MEGASCALE_KIND_RE.search(line)
        if not payload or not kind:
            continue
        nbytes = hlo_audit._shape_bytes(payload.group(1), payload.group(2))
        out[kind.group(1)] = out.get(kind.group(1), 0) + int(nbytes)
    return {k: int(v) for k, v in sorted(out.items())}


# ---------------------------------------------------------------------------
# Per-audit flow check + the gate entry point.
# ---------------------------------------------------------------------------


def audit_flow(audit, *, derived_file: dict | None = None,
               schedule_file: dict | None = None,
               graph: cg.CollectiveGraph | None = None,
               n_devices: int = 8, drift: bool = True) -> dict:
    """All structural detectors over one strategy audit.  Returns the
    per-strategy report fragment; ``problems`` is the flattened finding
    list the gate counts.  ``drift=False`` skips the derived-file pin
    comparison — the planner's ad-hoc spec candidates have no pinned
    declaration, only the structural detectors apply."""
    if graph is None:
        graph = cg.parse_graph(audit.compiled.as_text())
    meta = getattr(audit, "meta", None)
    detectors = {
        "redundant_pair": detect_redundant_pairs(graph),
        "wire_dtype": detect_wire_dtype(
            graph, meta.wire_dtype if meta else "f32",
            ignore_below=audit.budget.ignore_below),
        "replication": detect_replication(
            graph, meta.declared_leaves if meta else ()),
        "replica_groups": detect_replica_groups(
            graph, meta.mesh_dict if meta else {}),
        "census": census_cross_check(graph, audit.report),
        "exposed_comm": detect_exposed_comm(
            graph, bool(meta.declared_overlapped) if meta else False,
            ignore_below=audit.budget.ignore_below),
    }
    drift_p = budget_drift(audit, derived_file) if drift else []
    sched_drift = (schedule_drift(audit, schedule_file, graph=graph)
                   if drift else [])
    problems = ([f"[{audit.name}] {f}"
                 for fs in detectors.values() for f in fs]
                + drift_p + sched_drift)
    return {
        "graph": graph.summary(),
        "detectors": detectors,
        "derived": derive_budget(audit.report, audit.budget.ignore_below),
        "drift": drift_p,
        "schedule": derive_schedule_entry(
            graph, ignore_below=audit.budget.ignore_below),
        "schedule_drift": sched_drift,
        "overlap": overlap_score(
            graph, audit.report, n_devices=n_devices,
            ignore_below=audit.budget.ignore_below),
        "comm_split": comm_split(
            graph, audit.report,
            mesh_shape=meta.mesh_dict if meta else {},
            n_devices=n_devices),
        "problems": problems,
    }


def check(audits=None, *, n_devices: int = 8,
          derived_path: str = DERIVED_BUDGETS_PATH,
          schedule_path: str = DERIVED_SCHEDULE_PATH) -> list[str]:
    """Gate entry point: structural detectors + derived-budget and
    derived-schedule drift for every strategy this environment can
    compile.  ``audits`` reuses the CLI's already-compiled audit objects
    (one compile pays for both the ceiling audit and the flow check)."""
    if audits is None:
        from tpuframe.analysis import strategies

        audits = strategies.audit_all(n_devices)
    derived_file = load_derived(derived_path)
    schedule_file = load_derived_schedule(schedule_path)
    problems: list[str] = seeded_schedule_positive()
    for audit in audits:
        if audit.status == "unavailable" or audit.compiled is None:
            continue
        problems.extend(audit_flow(audit, derived_file=derived_file,
                                   schedule_file=schedule_file,
                                   n_devices=n_devices)["problems"])
    problems.extend(resize_drift(derived_file, n_devices=n_devices))
    return problems


# ---------------------------------------------------------------------------
# The --json report + obs-compare-style structural diffing.
# ---------------------------------------------------------------------------


def build_report(audits, *, lint_findings=(), n_devices: int = 8,
                 derived_path: str = DERIVED_BUDGETS_PATH,
                 schedule_path: str = DERIVED_SCHEDULE_PATH) -> dict:
    """Machine-readable gate report (schema pinned by tests — the
    ``--compare`` differ diffs two of these the way ``obs compare``
    diffs step times)."""
    derived_file = load_derived(derived_path)
    schedule_file = load_derived_schedule(schedule_path)
    strategies_out = []
    for audit in audits:
        entry = {
            "name": audit.name,
            "status": audit.status,
            "reason": audit.reason,
            "violations": list(audit.violations),
        }
        if audit.status != "unavailable" and audit.report is not None:
            flow = audit_flow(audit, derived_file=derived_file,
                              schedule_file=schedule_file,
                              n_devices=n_devices)
            entry.update({
                "collectives": flow["derived"]["kinds"],
                "total_bytes": flow["derived"]["total_bytes"],
                "derived": flow["derived"],
                "drift": flow["drift"],
                "detectors": {k: list(v)
                              for k, v in flow["detectors"].items()},
                "graph": flow["graph"],
                "schedule": flow["schedule"],
                "schedule_drift": flow["schedule_drift"],
                "overlap": flow["overlap"],
                "comm_split": flow["comm_split"],
            })
        strategies_out.append(entry)
    return {
        "schema": REPORT_SCHEMA,
        "jax": _jax_version(),
        "n_devices": int(n_devices),
        "lint": [{"rule": f.rule, "path": f.path, "line": f.line,
                  "message": f.message} for f in lint_findings],
        "strategies": strategies_out,
    }


def compare_reports(a: dict, b: dict, *,
                    bytes_tol: float = 0.10) -> tuple[int, list[str]]:
    """Structural diff of two --json reports (A = baseline, B =
    candidate).  rc 1 on a structural regression, 0 clean, 2 when no
    strategy overlaps — the ``obs compare`` return-code contract.

    Regression = a collective kind appears/disappears, a per-kind op
    count changes, per-kind bytes move more than ``bytes_tol``
    (relative), or a detector that was clean now finds something.

    Schedule section (participates only when BOTH reports carry it, so
    a schema-1 baseline still compares on the structural metrics): more
    exposed above-floor collectives, peak live bytes moving more than
    ``bytes_tol`` (relative), or overlap potential dropping by more
    than 0.10 are regressions.

    Comm-split section (same both-reports gate): DCN bytes growing more
    than ``bytes_tol`` (relative) — or any collective newly crossing
    slices on a strategy whose baseline DCN column was zero — is a
    regression.  One-sided by design: the DCN term is the one the
    hierarchical lowering exists to crush (PERF §23/§28), so a drop is
    the intended direction, never flagged.
    """
    lines: list[str] = []
    a_s = {s["name"]: s for s in a.get("strategies", [])
           if s.get("status") in ("ok", "violation") and "derived" in s}
    b_s = {s["name"]: s for s in b.get("strategies", [])
           if s.get("status") in ("ok", "violation") and "derived" in s}
    common = sorted(set(a_s) & set(b_s))
    if not common:
        return 2, ["no strategy audited in both reports — nothing to "
                   "compare"]
    regression = False
    for name in common:
        ka = a_s[name]["derived"]["kinds"]
        kb = b_s[name]["derived"]["kinds"]
        for kind in sorted(set(ka) | set(kb)):
            ea, eb = ka.get(kind), kb.get(kind)
            if ea is None:
                regression = True
                lines.append(f"REGRESSION {name}: new collective kind "
                             f"{kind} ({eb})")
                continue
            if eb is None:
                regression = True
                lines.append(f"REGRESSION {name}: collective kind {kind} "
                             f"disappeared (was {ea})")
                continue
            if ea["count"] != eb["count"]:
                regression = True
                lines.append(
                    f"REGRESSION {name}: {kind} op count "
                    f"{ea['count']} -> {eb['count']}")
            elif ea["bytes"] and (abs(eb["bytes"] - ea["bytes"])
                                  / ea["bytes"]) > bytes_tol:
                regression = True
                lines.append(
                    f"REGRESSION {name}: {kind} bytes "
                    f"{ea['bytes']} -> {eb['bytes']} "
                    f"({(eb['bytes'] - ea['bytes']) / ea['bytes']:+.1%} "
                    f"> ±{bytes_tol:.0%})")
        da = a_s[name].get("detectors", {})
        db = b_s[name].get("detectors", {})
        for det in sorted(set(da) | set(db)):
            na, nb = len(da.get(det, [])), len(db.get(det, []))
            if nb > na:
                regression = True
                lines.append(f"REGRESSION {name}: detector {det} findings "
                             f"{na} -> {nb}")
        sa, sb = a_s[name].get("schedule"), b_s[name].get("schedule")
        if sa and sb:
            ea = int(sa.get("exposed_above_floor", 0))
            eb = int(sb.get("exposed_above_floor", 0))
            if eb > ea:
                regression = True
                lines.append(f"REGRESSION {name}: exposed above-floor "
                             f"collectives {ea} -> {eb}")
            pa = int(sa.get("peak_live_bytes", 0))
            pb = int(sb.get("peak_live_bytes", 0))
            if pa and abs(pb - pa) / pa > bytes_tol:
                regression = True
                lines.append(
                    f"REGRESSION {name}: peak live bytes {pa} -> {pb} "
                    f"({(pb - pa) / pa:+.1%} > ±{bytes_tol:.0%})")
        oa, ob = a_s[name].get("overlap"), b_s[name].get("overlap")
        if oa and ob:
            va = float(oa.get("overlap_potential", 1.0))
            vb = float(ob.get("overlap_potential", 1.0))
            if va - vb > 0.10:
                regression = True
                lines.append(
                    f"REGRESSION {name}: overlap potential "
                    f"{va:.2f} -> {vb:.2f} (dropped > 0.10)")
        ca, cb = a_s[name].get("comm_split"), b_s[name].get("comm_split")
        if ca and cb:
            dcn_a = int(ca.get("dcn_bytes", 0))
            dcn_b = int(cb.get("dcn_bytes", 0))
            if dcn_a and (dcn_b - dcn_a) / dcn_a > bytes_tol:
                regression = True
                lines.append(
                    f"REGRESSION {name}: DCN bytes {dcn_a} -> {dcn_b} "
                    f"({(dcn_b - dcn_a) / dcn_a:+.1%} > +{bytes_tol:.0%})")
            elif not dcn_a and dcn_b:
                regression = True
                lines.append(
                    f"REGRESSION {name}: DCN bytes 0 -> {dcn_b} — "
                    f"collectives newly cross slices")
        if not any(ln.startswith(f"REGRESSION {name}:") for ln in lines):
            lines.append(f"ok {name}: collective structure unchanged")
    return (1 if regression else 0), lines


#: the keys every compiled strategy entry of a schema-2 report carries —
#: pinned here once so the selfcheck and the tests share one spelling.
STRATEGY_REPORT_KEYS = frozenset({
    "name", "status", "reason", "violations", "collectives",
    "total_bytes", "derived", "drift", "detectors", "graph",
    "schedule", "schedule_drift", "overlap", "comm_split",
})


def selfcheck(samples_dir: str = SAMPLES_COMPARE_DIR) -> list[str]:
    """Jax-free gate leg: the checked-in golden compare pair must keep
    exercising the differ's whole contract — base vs. base is rc 0,
    base vs. candidate is rc 1 *including a schedule-section line*, and
    the base report carries every schema-2 strategy key.  A report
    schema change that strands the differ fails CI before it ships."""
    base_path = os.path.join(samples_dir, "base.json")
    cand_path = os.path.join(samples_dir, "candidate.json")
    try:
        with open(base_path) as f:
            base = json.load(f)
        with open(cand_path) as f:
            cand = json.load(f)
    except (OSError, ValueError) as e:
        return [f"compare selfcheck: golden pair unreadable "
                f"({samples_dir}): {e}"]
    problems: list[str] = []
    if base.get("schema") != REPORT_SCHEMA:
        problems.append(
            f"compare selfcheck: golden base.json is schema "
            f"{base.get('schema')!r}, differ is at {REPORT_SCHEMA} — "
            f"regenerate the pair with --json")
    for s in base.get("strategies", []):
        if s.get("status") == "unavailable":
            continue
        missing = STRATEGY_REPORT_KEYS - set(s)
        if missing:
            problems.append(
                f"compare selfcheck: golden base.json strategy "
                f"{s.get('name')!r} lacks report keys {sorted(missing)}")
    rc, _ = compare_reports(base, base)
    if rc != 0:
        problems.append(
            f"compare selfcheck: base vs. base must be rc 0, got {rc}")
    rc, lines = compare_reports(base, cand)
    if rc != 1:
        problems.append(
            f"compare selfcheck: base vs. candidate must be rc 1 "
            f"(seeded regression), got {rc}")
    wanted = ("exposed above-floor", "peak live bytes",
              "overlap potential")
    if not any(any(w in ln for w in wanted) for ln in lines):
        problems.append(
            "compare selfcheck: base vs. candidate found no "
            "schedule-section regression — the differ lost the "
            "schedule plane")
    if not any("DCN bytes" in ln for ln in lines):
        problems.append(
            "compare selfcheck: base vs. candidate found no comm-split "
            "regression — the differ lost the DCN plane (the golden "
            "candidate seeds a slice-crossing dp all-reduce)")
    return problems


def _jax_version() -> str:
    try:
        import jax

        return jax.__version__
    except Exception:  # noqa: BLE001 — report stays buildable without jax
        return "unknown"
