"""Analysis for a crash/resume convergence run of `python -m tpuframe.train`
(train, injected crash, async-ckpt resume) — turns the raw JSONL metric
logs into the convergence assertions the round-3 verdict asked for (loss
curve decreasing across the crash and resume; throughput held).

Pure host-side: no jax import, safe to run anytime.  Prints one JSON
object (committed as perf/results/conv_summary.json) with pass/fail per
assertion so the claim is checkable from the artifact alone.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
RES = os.environ.get("CONV_RESULTS_DIR", os.path.join(HERE, "results"))

# Expected run shape (the 600-step run's numbers; overridable so the
# analysis logic itself is testable on a miniature CPU run).
FAULT_STEP = int(os.environ.get("CONV_FAULT_STEP", "350"))
CKPT_EVERY = int(os.environ.get("CONV_CKPT_EVERY", "150"))
LOG_EVERY = int(os.environ.get("CONV_LOG_EVERY", "10"))
RESUME_STEP = (FAULT_STEP // CKPT_EVERY) * CKPT_EVERY


def read_jsonl(name: str, prefix: str = "train") -> list[dict]:
    path = os.path.join(RES, name)
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("prefix") == prefix:
                out.append(rec)
    return out


def windowed_means(series: list[tuple[int, float]], k: int = 5):
    """Mean loss over consecutive windows of k logged points."""
    vals = [v for _, v in series]
    return [sum(vals[i:i + k]) / len(vals[i:i + k])
            for i in range(0, len(vals), k)]


def main() -> int:
    a = read_jsonl("conv_a.jsonl")
    b = read_jsonl("conv_b.jsonl")
    r50 = read_jsonl("conv_r50.jsonl")
    evals = read_jsonl("conv_a.jsonl", "eval") + read_jsonl("conv_b.jsonl",
                                                            "eval")
    summary: dict = {"experiment": "convergence+crash-resume (round 4)"}
    ok = True

    # --- A: the cifar run, killed at 350, resumed from ckpt-300 ---------
    la = [(r["step"], r["loss"]) for r in a if "loss" in r]
    lb = [(r["step"], r["loss"]) for r in b if "loss" in r]
    if not la or not lb:
        summary["cifar"] = {"ok": False,
                            "error": f"missing logs (A={len(la)} B={len(lb)})"}
        print(json.dumps(summary, indent=1))
        return 1

    last_a = max(s for s, _ in la)
    first_b = min(s for s, _ in lb)
    # The run must resume from SOME committed checkpoint at or below the
    # last one written before the crash — with ckpt_async the step-RESUME
    # snapshot's COMMIT may legitimately not be durable when os._exit
    # fires, in which case falling back to the previous committed ckpt is
    # exactly the torn-checkpoint contract, not a failure.
    resume_base = ((first_b - 1) // CKPT_EVERY) * CKPT_EVERY
    resume_gap_ok = (CKPT_EVERY <= resume_base <= RESUME_STEP
                     and first_b - resume_base <= LOG_EVERY
                     and FAULT_STEP - LOG_EVERY <= last_a < FAULT_STEP)
    # Loss continuity across the crash: first resumed window vs last
    # pre-crash window (resume replays steps RESUME..FAULT with identical
    # data order, so the curve should CONTINUE, not reset to init-level).
    tail_a = [v for s, v in la if s > resume_base]
    head_b = [v for s, v in lb if s <= FAULT_STEP]
    init_a = [v for s, v in la if s <= 3 * LOG_EVERY]
    continuity_ok = bool(tail_a and head_b and
                         abs(sum(head_b) / len(head_b)
                             - sum(tail_a) / len(tail_a))
                         < 0.25 * max(1e-9, sum(init_a) / len(init_a)
                                      - sum(tail_a) / len(tail_a)))

    full = sorted(la + [p for p in lb if p[0] > last_a])
    wm = windowed_means(full, 5)
    drops = sum(1 for i in range(1, len(wm)) if wm[i] < wm[i - 1])
    decreasing_ok = (wm[-1] < wm[0] and full[-1][1] < 0.5 * full[0][1]
                     and drops >= 0.7 * (len(wm) - 1))

    warm_cut = int(os.environ.get("CONV_WARM_STEP", "100"))
    rates = [r["examples_per_sec"] for r in (a + b)
             if "examples_per_sec" in r and r["step"] > warm_cut]
    if rates:
        mean_r = sum(rates) / len(rates)
        var = sum((x - mean_r) ** 2 for x in rates) / len(rates)
        cv = (var ** 0.5) / mean_r
    else:
        mean_r, cv = 0.0, 1.0

    acc = [(r["step"], r.get("accuracy")) for r in evals
           if r.get("accuracy") is not None]
    # Throughput must HOLD across the run (the verdict's "within 5%"): gate
    # on the relative spread of the post-warmup per-window rates.
    throughput_ok = bool(rates and cv < 0.05)
    summary["cifar"] = {
        "ok": bool(resume_gap_ok and continuity_ok and decreasing_ok
                   and throughput_ok),
        "steps_logged": len(full),
        "last_step_before_crash": last_a,
        "first_step_after_resume": first_b,
        "resumed_from_ckpt_step": resume_base,
        "resume_from_committed_ckpt_ok": resume_gap_ok,
        "loss_first": round(full[0][1], 4),
        "loss_at_crash": round(tail_a[-1], 4) if tail_a else None,
        "loss_final": round(full[-1][1], 4),
        "windowed_means": [round(v, 4) for v in wm],
        "curve_decreasing_ok": decreasing_ok,
        "loss_continuity_across_crash_ok": continuity_ok,
        "eval_accuracy": [(s, round(v, 4)) for s, v in acc],
        "throughput_mean_ex_per_sec": round(mean_r, 1),
        "throughput_cv": round(cv, 4),
        "throughput_steady_ok": throughput_ok,
    }
    ok &= summary["cifar"]["ok"]

    # --- B: resnet50 sustained run vs the bench steady state -----------
    if r50:
        lr50 = [(r["step"], r["loss"]) for r in r50 if "loss" in r]
        rates50 = [r["examples_per_sec_per_chip"] for r in r50
                   if "examples_per_sec_per_chip" in r
                   and r["step"] > warm_cut]
        bench_val = None
        try:
            with open(os.path.join(RES, "bench_b256.out")) as fh:
                bench_val = json.loads(
                    fh.read().strip().splitlines()[-1])["value"]
        except Exception:
            pass
        steady = (sorted(rates50)[len(rates50) // 2] if rates50 else 0.0)
        wm50 = windowed_means(sorted(lr50), 5)
        summary["resnet50_synthetic"] = {
            "steps_logged": len(lr50),
            "loss_first": round(lr50[0][1], 4) if lr50 else None,
            "loss_final": round(lr50[-1][1], 4) if lr50 else None,
            "windowed_means": [round(v, 4) for v in wm50],
            "curve_decreasing_ok": bool(wm50 and wm50[-1] < wm50[0]),
            "harness_img_per_sec_per_chip_median": round(steady, 1),
            "bench_device_only_img_per_sec": bench_val,
            "harness_vs_bench": (round(steady / bench_val, 4)
                                 if bench_val else None),
        }
        # The harness number includes the real input pipeline + logging; vs
        # bench.py's device-only loop.  Record the ratio rather than
        # asserting 0.95 blindly — if infeed dominates, that is a finding
        # to report, not to hide.
        ok &= bool(wm50 and wm50[-1] < wm50[0])

    summary["ok"] = bool(ok)
    print(json.dumps(summary, indent=1))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
