"""``python -m tpuframe.analysis`` — the offline CI gate.

Runs all three analysis layers against the shipped tree and exits
non-zero on any finding:

  1. source lint (TF101-TF106) over ``tpuframe/``;
  2. per-strategy collective budget audits — every strategy step program
     in :mod:`tpuframe.analysis.strategies` is AOT-compiled on a forced
     multi-device CPU backend and its collectives checked against the
     declared :class:`~tpuframe.analysis.budgets.CommBudget`;
  3. registry cross-checks — every
     :data:`~tpuframe.analysis.budgets.KNOWN_VMEM_EXCLUSIONS` entry must
     still be excluded by the gate it cites;
  4. tune self-check — the roofline hardware tables must keep
     reproducing PERF.md §2's recorded anchors, the shipped tuning DB
     (if any) must validate against the schema, and the tuner's own
     flag plumbing must pass TF106 (``tpuframe.tune.check``);
  5. obs self-check — ``python -m tpuframe.obs summarize --selfcheck``
     schema-validates the shipped sample event logs (docs/samples/), so
     an event-schema change that strands existing logs fails CI before
     it ships;
  6. mem self-check — the remat policy registry must apply every preset,
     ``save_named`` must parse (and reject unknown seams), and the
     model/step files must pass the TF108 registry-seam lint
     (``tpuframe.mem.check``);
  7. shardflow — the structural detectors of
     :mod:`tpuframe.analysis.shardflow` (redundant collective pairs,
     wire-dtype, accidental replication, replica-group consistency,
     exposed communication) run over the collective-flow graph of every
     compiled strategy; the auto-derived per-kind budgets are
     drift-checked against the checked-in ``derived_budgets.json``
     (regenerate with ``--emit-budgets``) and the schedule/liveness
     records against ``derived_schedule.json`` (regenerate with
     ``--emit-schedule``);
  8. pspec self-check — the declarative parallelism-spec grammar
     (:mod:`tpuframe.parallel.pspec`) fuzzes its pinned parse/format
     round-trip and rejection tables, and seeds a replica-group
     mismatch against the hierarchical ICI×DCN mesh that the detector
     MUST flag (plus a valid cross-slice twin whose bytes the ICI/DCN
     split must attribute to DCN) — the gate refuses to run blind;
  9. compare selfcheck — the jax-free golden compare pair under
     ``docs/samples/analysis_compare/`` must keep exercising the whole
     ``--compare`` contract (schema keys, rc codes, the schedule
     section), so a report-schema change that strands the differ fails
     CI before it ships;
  10. rollout self-check — the live-rollout controller
      (:mod:`tpuframe.serve.rollout`) replays its full state machine on
      a simulated fleet (drain→swap→readmit ordering, zero loss, zero
      compile misses, all replicas on the target version), runs the
      TF121 swap-seam lint over the tree, checks the rollout event
      registrations and the ``gate_compare`` rc contract, and seeds a
      poisoned canary that MUST auto-roll back naming the failing
      metric — the promotion gate refuses to run blind;
  11. plan self-check — the pinned ``tune plan`` report
     (``perf/results/plan_report_*``) must schema-validate, its ranking
     must re-derive from its own rows with every ranked candidate
     detector-clean, a seeded best/worst cost swap must flip the
     derived ranking (the gate refuses to rank blind), and the three
     pinned PERF verdicts (§18/§20/§23) must re-derive AND hold
     (``tpuframe.tune.plan.check``; version-skew skips itself like
     ``--emit-budgets``).
  12. fusion self-check — the bucketed-fusion pass
     (:mod:`tpuframe.parallel.fusion`) checks its env-knob parse, its
     bucket-census arithmetic (ordered partition, kind-homogeneous,
     byte-cap), seeds an all-exposed but ``declared_overlapped``
     program that ``detect_exposed_comm`` MUST fail (the live gate
     refuses to run blind), and on a multi-device backend pins the
     psum-linearity identity: per-leaf, packed, and staged reductions
     agree to 1e-6.
  13. trace self-check — the request-tracing plane
     (:mod:`tpuframe.obs.tracing`) cross-pins its span schema against
     ``obs/events.py``'s registry, runs the TF123 tracing-seam lint
     over the tree, round-trips a synthetic healthy trace (exactly one
     complete root, verifier-clean), seeds leaked-span / orphan-span /
     TTFT-mismatch positives the verifier MUST flag (the trace gate
     refuses to run blind), reconstructs the golden traced-fleet
     sample (``docs/samples/traced_fleet/``) clean with a resolvable
     p99 exemplar, and checks the SLO sentry's default specs and its
     rc contract (``tpuframe.obs.tracing.check``).
  14. hier self-check — the hierarchical two-level collective seam
     (:mod:`tpuframe.parallel.hier`) validates its mode registry and
     env parsing, pins a seeded flat/two-level HLO pair against the
     ICI/DCN byte split (the two-level lowering MUST move the
     cross-slice term down by n_inner), proves the two-level mean
     equals the flat mean to 1e-6 on a multi-device slice mesh, runs
     the TF124 cross-slice seam lint over the tree, and seeds a
     known-bad raw cross-slice collective the lint MUST flag (the
     seam gate refuses to run blind).

``--json PATH`` writes the whole gate outcome as a schema-pinned report;
``--compare A.json B.json`` diffs two such reports for structural
collective regressions (rc 1 regression / 0 clean / 2 no overlap — the
``obs compare`` contract) without touching jax at all; ``--selfcheck``
runs only legs 9 and 11 plus fusion's jax-free subset (version stamp
aside, no backend).

Strategies this interpreter cannot express (see
:class:`~tpuframe.analysis.strategies.Unavailable`) print as SKIP and do
not fail the gate.

The strategy audits need a multi-device jax backend, so the CLI
re-executes itself in a child process with a scrubbed CPU-only
environment (``JAX_PLATFORMS=cpu``, forced host device count, no TPU
plugin) — the same pattern as the repo's multichip dry run.  Pass
``--lint-only`` to skip the jax-dependent layers entirely (no re-exec,
no jax import).
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

_CHILD_FLAG = "TPUFRAME_ANALYSIS_CHILD"


def _scrubbed_cpu_env(n_devices: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags).strip()
    env["PYTHONUNBUFFERED"] = "1"
    env[_CHILD_FLAG] = "1"
    return env


def _parse(argv):
    ap = argparse.ArgumentParser(
        prog="python -m tpuframe.analysis",
        description="static SPMD/collective analysis (offline CI gate)")
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/dirs to lint (default: the tpuframe "
                         "package directory)")
    ap.add_argument("--lint-only", action="store_true",
                    help="run only the AST source lint (no jax)")
    ap.add_argument("--strategy", action="append", default=None,
                    metavar="NAME",
                    help="audit only these strategies (repeatable)")
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual CPU device count for the strategy "
                         "audits (default 8)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the gate outcome as a "
                         "machine-readable report (schema-pinned)")
    ap.add_argument("--emit-budgets", action="store_true",
                    help="regenerate tpuframe/analysis/"
                         "derived_budgets.json from the compiled "
                         "strategies (the drift check's declarations)")
    ap.add_argument("--emit-schedule", action="store_true",
                    help="regenerate tpuframe/analysis/"
                         "derived_schedule.json (per-strategy "
                         "liveness/overlap-window records) from the "
                         "compiled strategies")
    ap.add_argument("--selfcheck", action="store_true",
                    help="validate the golden --compare pair and the "
                         "pinned report schema (no jax), then exit")
    ap.add_argument("--compare", nargs=2, metavar=("A", "B"),
                    default=None,
                    help="diff two --json reports for structural "
                         "collective regressions (no jax; rc 1 "
                         "regression, 0 clean, 2 no overlap)")
    ap.add_argument("--bytes-tol", type=float, default=0.10,
                    help="relative per-kind byte tolerance for "
                         "--compare (default 0.10)")
    return ap.parse_args(argv)


def _default_lint_paths() -> list[str]:
    import tpuframe

    return [os.path.dirname(os.path.abspath(tpuframe.__file__))]


def _run_lint(paths) -> list:
    from tpuframe.analysis.source_lint import lint_paths

    findings = lint_paths(paths)
    for f in findings:
        print(f"LINT {f}")
    print(f"[analysis] source lint: {len(findings)} finding(s) over "
          f"{', '.join(map(str, paths))}")
    return findings


def _run_strategies(names, n_devices) -> tuple[int, list]:
    from tpuframe.analysis import strategies

    failures = 0
    audits = strategies.audit_all(n_devices, names)
    for audit in audits:
        print(f"[analysis] {audit}")
        if audit.status == "violation":
            failures += len(audit.violations) or 1
    return failures, audits


def _run_shardflow(audits, n_devices, *, emit: bool,
                   emit_schedule: bool) -> int:
    from tpuframe.analysis import shardflow

    if emit:
        shardflow.emit_derived(audits, n_devices=n_devices)
        print(f"[analysis] wrote {shardflow.DERIVED_BUDGETS_PATH}")
    if emit_schedule:
        shardflow.emit_schedule(audits, n_devices=n_devices)
        print(f"[analysis] wrote {shardflow.DERIVED_SCHEDULE_PATH}")
    problems = shardflow.check(audits, n_devices=n_devices)
    for p in problems:
        print(f"FLOW {p}")
    print(f"[analysis] shardflow: {len(problems)} problem(s) over "
          f"{sum(1 for a in audits if a.compiled is not None)} "
          f"compiled strategy program(s)")
    return len(problems)


def _run_compare(path_a, path_b, bytes_tol) -> int:
    import json

    from tpuframe.analysis import shardflow

    with open(path_a) as f:
        a = json.load(f)
    with open(path_b) as f:
        b = json.load(f)
    rc, lines = shardflow.compare_reports(a, b, bytes_tol=bytes_tol)
    for line in lines:
        print(line)
    return rc


def _write_json(path, audits, lint_findings, n_devices) -> None:
    import json

    from tpuframe.analysis import shardflow

    report = shardflow.build_report(audits, lint_findings=lint_findings,
                                    n_devices=n_devices)
    with open(path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"[analysis] wrote {path}")


def _run_tune_check() -> int:
    from tpuframe import tune

    problems = tune.check()
    for p in problems:
        print(f"TUNE {p}")
    print(f"[analysis] tune self-check: {len(problems)} problem(s)")
    return len(problems)


def _run_mem_check() -> int:
    from tpuframe import mem

    problems = mem.check()
    for p in problems:
        print(f"MEM {p}")
    print(f"[analysis] mem self-check: {len(problems)} problem(s)")
    return len(problems)


def _run_serve_check() -> int:
    from tpuframe import serve

    problems = serve.check()
    for p in problems:
        print(f"SERVE {p}")
    print(f"[analysis] serve self-check: {len(problems)} problem(s)")
    return len(problems)


def _run_zero1_check() -> int:
    from tpuframe.parallel import zero1

    problems = zero1.check()
    for p in problems:
        print(f"ZERO1 {p}")
    print(f"[analysis] zero1 self-check: {len(problems)} problem(s)")
    return len(problems)


def _run_fusion_check() -> int:
    from tpuframe.parallel import fusion

    problems = fusion.check()
    for p in problems:
        print(f"FUSION {p}")
    print(f"[analysis] fusion self-check: {len(problems)} problem(s)")
    return len(problems)


def _run_fusion_static() -> int:
    # Jax-free subset: env-knob parse, bucket-census arithmetic, the
    # seeded zero-overlap positive against the live exposed-comm gate.
    from tpuframe.parallel import fusion

    problems = fusion.check_static()
    for p in problems:
        print(f"FUSION {p}")
    print(f"[analysis] fusion static self-check: {len(problems)} "
          f"problem(s)")
    return len(problems)


def _run_elastic_check() -> int:
    from tpuframe import elastic

    problems = elastic.check()
    for p in problems:
        print(f"ELASTIC {p}")
    print(f"[analysis] elastic self-check: {len(problems)} problem(s)")
    return len(problems)


def _run_hier_check() -> int:
    from tpuframe.parallel import hier

    problems = hier.check()
    for p in problems:
        print(f"HIER {p}")
    print(f"[analysis] hier self-check: {len(problems)} problem(s)")
    return len(problems)


def _run_pspec_check() -> int:
    from tpuframe.parallel import pspec

    problems = pspec.check()
    for p in problems:
        print(f"PSPEC {p}")
    print(f"[analysis] pspec self-check: {len(problems)} problem(s)")
    return len(problems)


def _run_plan_check() -> int:
    # Jax-light: validates the pinned planner report (schema pin,
    # re-derivable ranking, seeded ranking-drift positive, the three
    # pinned PERF verdicts) — jax is touched only for the version stamp.
    from tpuframe.tune import plan

    problems = plan.check()
    for p in problems:
        print(f"PLAN {p}")
    print(f"[analysis] plan self-check: {len(problems)} problem(s)")
    return len(problems)


def _run_router_check() -> int:
    from tpuframe.serve import router

    problems = router.check()
    for p in problems:
        print(f"ROUTER {p}")
    print(f"[analysis] router self-check: {len(problems)} problem(s)")
    return len(problems)


def _run_rollout_check() -> int:
    from tpuframe.serve import rollout

    problems = rollout.check()
    for p in problems:
        print(f"ROLLOUT {p}")
    print(f"[analysis] rollout self-check: {len(problems)} problem(s)")
    return len(problems)


def _run_trace_check() -> int:
    from tpuframe.obs import tracing

    problems = tracing.check()
    for p in problems:
        print(f"TRACE {p}")
    print(f"[analysis] trace self-check: {len(problems)} problem(s)")
    return len(problems)


def _run_obs_check() -> int:
    # Through the real CLI entry point, not an import — the gate then
    # also catches a broken ``python -m tpuframe.obs`` invocation.
    rc = subprocess.call([sys.executable, "-m", "tpuframe.obs",
                          "summarize", "--selfcheck"])
    if rc:
        print(f"[analysis] obs selfcheck FAILED (rc {rc})")
    return 1 if rc else 0


def _run_flow_selfcheck() -> int:
    # Jax-free: pure JSON over the checked-in golden compare pair.
    from tpuframe.analysis import shardflow

    problems = shardflow.selfcheck()
    for p in problems:
        print(f"SELFCHECK {p}")
    print(f"[analysis] compare selfcheck: {len(problems)} problem(s)")
    return len(problems)


def _run_registry_checks() -> int:
    from tpuframe.analysis.budgets import check_known_exclusions

    problems = check_known_exclusions()
    for p in problems:
        print(f"REGISTRY {p}")
    print(f"[analysis] known-exclusion registry: "
          f"{len(problems)} problem(s)")
    return len(problems)


def main(argv=None) -> int:
    args = _parse(argv if argv is not None else sys.argv[1:])
    lint_paths_arg = args.paths or _default_lint_paths()

    if args.compare:
        # Pure JSON diffing — no jax, no re-exec, usable anywhere.
        return _run_compare(args.compare[0], args.compare[1],
                            args.bytes_tol)

    if args.selfcheck:
        # Also jax-free: golden-pair + schema validation, plus the
        # planner-report pin (version-skew skips itself).
        return 1 if (_run_flow_selfcheck() + _run_plan_check()
                     + _run_fusion_static()) else 0

    if (args.emit_budgets or args.emit_schedule) and args.strategy:
        print("[analysis] --emit-budgets/--emit-schedule regenerate the "
              "whole declaration file and cannot be combined with "
              "--strategy")
        return 2

    if not args.lint_only and os.environ.get(_CHILD_FLAG) != "1":
        # Re-exec with a clean multi-device CPU backend; the child runs
        # this same main() with _CHILD_FLAG set.
        cmd = [sys.executable, "-m", "tpuframe.analysis",
               "--devices", str(args.devices)]
        for s in args.strategy or ():
            cmd += ["--strategy", s]
        if args.json:
            cmd += ["--json", args.json]
        if args.emit_budgets:
            cmd += ["--emit-budgets"]
        if args.emit_schedule:
            cmd += ["--emit-schedule"]
        cmd += args.paths or []
        return subprocess.call(cmd, env=_scrubbed_cpu_env(args.devices))

    lint_findings = _run_lint(lint_paths_arg)
    n_findings = len(lint_findings)
    if not args.lint_only:
        strat_failures, audits = _run_strategies(
            tuple(args.strategy) if args.strategy else None, args.devices)
        n_findings += strat_failures
        n_findings += _run_shardflow(audits, args.devices,
                                     emit=args.emit_budgets,
                                     emit_schedule=args.emit_schedule)
        n_findings += _run_flow_selfcheck()
        n_findings += _run_registry_checks()
        n_findings += _run_tune_check()
        n_findings += _run_mem_check()
        n_findings += _run_serve_check()
        n_findings += _run_router_check()
        n_findings += _run_rollout_check()
        n_findings += _run_zero1_check()
        n_findings += _run_fusion_check()
        n_findings += _run_elastic_check()
        n_findings += _run_hier_check()
        n_findings += _run_pspec_check()
        n_findings += _run_plan_check()
        n_findings += _run_trace_check()
        n_findings += _run_obs_check()
        if args.json:
            _write_json(args.json, audits, lint_findings, args.devices)

    if n_findings:
        print(f"[analysis] FAIL: {n_findings} finding(s)")
        return 1
    print("[analysis] clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
