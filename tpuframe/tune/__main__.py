"""CLI for the offline autotuner.

    python -m tpuframe.tune sweep --topology v5e:2x2   # the whole thing
    python -m tpuframe.tune plan                        # spec planner
    python -m tpuframe.tune sweep --remat               # remat policy search
    python -m tpuframe.tune sweep --serve               # serving decode grid
    python -m tpuframe.tune sweep --zero1               # weight-update sharding
    python -m tpuframe.tune sweep --fusion              # fusion bucket grid
    python -m tpuframe.tune sweep --hier                # two-level collectives
    python -m tpuframe.tune show                        # ranked DB contents
    python -m tpuframe.tune check                       # CI self-check

Runs CPU-only: the sweep compiles against a compile-only TPU topology on
the CPU host — no chip.  The env set-up below pins the CPU backend and
forces real Mosaic lowering for pallas kernels; it must run before jax
initializes a backend.
"""

import argparse
import json
import os
import sys


def _ensure_cpu_env() -> None:
    """CPU-host env set-up (perf/_common.ensure_cpu_backend's rule).

    jax is imported by the tpuframe package root before this runs, but the
    backend is chosen lazily — re-exec is only needed when JAX_PLATFORMS
    was already forced to something other than cpu.
    """
    os.environ.setdefault("TPUFRAME_PALLAS_INTERPRET", "0")
    # Off-GCP hosts: libtpu's topology init otherwise polls the GCE
    # metadata server 30x per variable (~minutes of 403s) before giving up.
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    if os.environ.get("JAX_PLATFORMS", "") not in ("", "cpu"):
        print("[tune] re-exec on the plain CPU backend...", flush=True)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.execvpe(sys.executable,
                   [sys.executable, "-m", "tpuframe.tune"] + sys.argv[1:],
                   os.environ)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _cmd_sweep(args) -> int:
    from tpuframe.tune import search

    if args.serve:
        search.serve_sweep(args.topology, db_path=args.db,
                           report_path=args.report,
                           blocks=tuple(args.serve_blocks),
                           slots_grid=tuple(args.serve_slots))
        return 0
    if args.remat:
        search.remat_sweep(args.topology, db_path=args.db,
                           report_path=args.report,
                           batch=args.remat_batch,
                           policies=tuple(args.remat_policies)
                           if args.remat_policies else None)
        return 0
    if args.zero1:
        search.zero1_sweep(args.topology, db_path=args.db,
                           report_path=args.report,
                           batch=args.zero1_batch)
        return 0
    if args.fusion:
        search.fusion_sweep(args.topology, db_path=args.db,
                            report_path=args.report,
                            batch=args.fusion_batch,
                            thresholds=tuple(args.fusion_thresholds))
        return 0
    if args.hier:
        search.hier_sweep(args.topology, slices=args.hier_slices,
                          db_path=args.db, report_path=args.report,
                          batch=args.hier_batch)
        return 0
    search.sweep(args.topology, db_path=args.db, report_path=args.report,
                 seq=args.seq, head_dim=args.head_dim,
                 blocks=tuple(args.blocks),
                 bench_batches=tuple(args.bench_batches))
    return 0


def _cmd_fusion_probe(args) -> int:
    import json

    from tpuframe.tune import search

    row = search._fusion_probe_row(args.topology, args.program,
                                   args.batch, args.threshold, args.floor)
    with open(args.out, "w") as f:
        json.dump(row, f)
    return 0


def _cmd_hier_probe(args) -> int:
    import json

    from tpuframe.tune import search

    payload = search._hier_probe_row(args.topology, args.slices,
                                     args.program, args.batch, args.mode,
                                     args.hier)
    with open(args.out, "w") as f:
        json.dump(payload, f)
    return 0


def _cmd_plan(args) -> int:
    from tpuframe.tune import plan as plan_lib

    report = plan_lib.plan(args.topology,
                           slice_counts=tuple(args.slices),
                           db_path=args.db, report_path=args.report)
    return 0 if report.get("winner") else 1


def _cmd_show(args) -> int:
    from tpuframe.tune import db as tune_db

    path = args.db or tune_db.default_db_path()
    if not os.path.exists(path):
        print(f"no tuning DB at {path}")
        return 1
    db = tune_db.TuningDB.open(path)
    for fam in sorted({r.family for r in db.records()}):
        print(f"[{fam}]")
        for rec in db.top_k(10, family=fam):
            tier = ("measured" if rec.measured
                    and rec.measured.get("value") is not None
                    else "predicted")
            print(f"  {rec.program} {rec.generation} "
                  f"{json.dumps(rec.config, sort_keys=True)} "
                  f"-> {rec.predicted.get('predicted_ms')} ms "
                  f"({tier})")
    return 0


def _cmd_check(args) -> int:
    """Self-check the analysis gate registers: hardware-table sanity, DB
    schema validation, TF106 self-lint of the tuner's own flag plumbing."""
    from tpuframe.tune import check as run_check

    problems = run_check(db_path=args.db)
    for p in problems:
        print(f"[tune-check] {p}")
    print(f"[tune-check] {'FAIL' if problems else 'OK'}")
    return 1 if problems else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m tpuframe.tune",
                                 description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    sw = sub.add_parser("sweep", help="offline AOT sweep on a compile-only "
                                      "topology")
    sw.add_argument("--topology", default="v5e:2x2")
    sw.add_argument("--db", default=None, help="tuning DB path "
                    "(default: <repo>/tune_db.json)")
    sw.add_argument("--report", default=None)
    sw.add_argument("--seq", type=int, default=2048)
    sw.add_argument("--head-dim", type=int, default=64)
    sw.add_argument("--blocks", type=int, nargs="+",
                    default=[128, 256, 512])
    sw.add_argument("--bench-batches", type=int, nargs="+", default=[256])
    sw.add_argument("--serve", action="store_true",
                    help="sweep serving decode block sizes x slot counts "
                         "(serve_lm family) over the AOT decode step "
                         "instead of the fa/xla-opts grid")
    sw.add_argument("--serve-blocks", type=int, nargs="+",
                    default=[64, 128, 256], metavar="BLOCK")
    sw.add_argument("--serve-slots", type=int, nargs="+",
                    default=[8, 16], metavar="SLOTS")
    sw.add_argument("--remat", action="store_true",
                    help="sweep tpuframe.mem remat policies over the "
                         "donated ResNet-50 train step (bytes objective) "
                         "instead of the fa/xla-opts grid")
    sw.add_argument("--remat-batch", type=int, default=512)
    sw.add_argument("--zero1", action="store_true",
                    help="sweep weight-update sharding (replicated vs "
                         "ZeRO-1) over the donated ResNet-50 + BERT train "
                         "steps (weight_update_* families)")
    sw.add_argument("--zero1-batch", type=int, default=512)
    sw.add_argument("--fusion", action="store_true",
                    help="sweep gradient-fusion bucket thresholds over "
                         "the donated ResNet-50 DP train step, ranked by "
                         "overlap score + compiled wire bytes "
                         "(fusion_threshold family)")
    sw.add_argument("--fusion-batch", type=int, default=512)
    sw.add_argument("--hier", action="store_true",
                    help="sweep two-level collectives on a compile-only "
                         "MULTI-slice topology (flat vs hier), ranked "
                         "on step + ICI + DCN ms (hier_collectives "
                         "family)")
    sw.add_argument("--hier-batch", type=int, default=512)
    sw.add_argument("--hier-slices", type=int, default=2,
                    help="slice count for the compile-only multi-slice "
                         "topology (PJRT num_slices)")
    sw.add_argument("--fusion-thresholds", type=int, nargs="+",
                    default=[16384, 32768, 65536, 131072, 262144],
                    metavar="BYTES")
    sw.add_argument("--remat-policies", nargs="+", default=None,
                    metavar="POLICY")
    sw.set_defaults(fn=_cmd_sweep)

    pl = sub.add_parser("plan", help="static auto-parallelism planner: "
                                     "enumerate specs, AOT-compile on a "
                                     "compile-only topology, gate on the "
                                     "shardflow detectors, rank by the "
                                     "cost stack")
    pl.add_argument("--topology", default="v5e:2x2")
    pl.add_argument("--slices", type=int, nargs="+", default=[1, 2],
                    help="slice counts to plan over (DCN hierarchy)")
    pl.add_argument("--db", default=None, help="tuning DB path "
                    "(default: <repo>/tune_db.json)")
    pl.add_argument("--report", default=None)
    pl.set_defaults(fn=_cmd_plan)

    # Hidden worker: one fusion candidate per process, because libtpu's
    # fusion emitter can SIGABRT on a bucket shape and the parent sweep
    # must survive to record the crash (fusion_sweep spawns these; the
    # parent holds the AOT lock, so the probe never takes it).
    fp = sub.add_parser("_fusion-probe")
    fp.add_argument("--topology", default="v5e:2x2")
    fp.add_argument("--program", default="resnet50")
    fp.add_argument("--batch", type=int, default=512)
    fp.add_argument("--floor", type=int, default=1024)
    fp.add_argument("--threshold", type=int, default=None)
    fp.add_argument("--out", required=True)
    fp.set_defaults(fn=_cmd_fusion_probe)

    # Hidden worker: one hier candidate per process — the compile-only
    # multi-slice backend wedges nondeterministically, and the parent
    # sweep must survive a timeout to retry/record it (hier_sweep
    # spawns these; the parent holds the AOT lock, the probe doesn't).
    hp = sub.add_parser("_hier-probe")
    hp.add_argument("--topology", default="v5e:2x2")
    hp.add_argument("--slices", type=int, default=2)
    hp.add_argument("--program", default="lm")
    hp.add_argument("--batch", type=int, default=512)
    hp.add_argument("--mode", default="replicated")
    hp.add_argument("--hier", default="flat")
    hp.add_argument("--out", required=True)
    hp.set_defaults(fn=_cmd_hier_probe)

    sh = sub.add_parser("show", help="print ranked DB contents")
    sh.add_argument("--db", default=None)
    sh.set_defaults(fn=_cmd_show)

    ck = sub.add_parser("check", help="CI self-check (schema + tables + "
                                      "TF106 self-lint)")
    ck.add_argument("--db", default=None)
    ck.set_defaults(fn=_cmd_check)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    _ensure_cpu_env()
    sys.exit(main())
