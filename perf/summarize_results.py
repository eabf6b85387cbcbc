"""Summarize perf/results/* into BASELINE.md-ready markdown.

Pure host-side (no jax).  Run anytime; prints only what exists, each
row stamped with its file's mtime so stale artifacts (e.g. a round-3
fa_tpu_tests.out next to a fresh fa_tpu_tests2.out) are tell-apart-able
at a glance.  The point is to turn a narrow chip window into committed
BASELINE rows fast instead of hand-formatting.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
import time

RES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")


def mtime(path) -> str:
    return time.strftime("%m-%d %H:%M", time.gmtime(os.path.getmtime(path)))


def last_json_line(path):
    try:
        for line in reversed(open(path).read().strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)
    except (OSError, json.JSONDecodeError):
        pass
    return None


def bench_rows():
    rows = []
    for f in sorted(glob.glob(os.path.join(RES, "bench_*.out"))):
        rec = last_json_line(f)
        if not rec or "value" not in rec:
            continue
        name = os.path.basename(f)[len("bench_"):-len(".out")]
        flag = " (DEGRADED)" if rec.get("degraded") else ""
        mfu = f", mfu {rec['mfu']:.1%}" if "mfu" in rec else ""
        rows.append(f"| {name} | {rec['value']}{flag} | "
                    f"{rec.get('unit', '')}{mfu} | {mtime(f)} | "
                    f"perf/results/{os.path.basename(f)} |")
    if rows:
        print("\n### bench.py (ResNet-50 img/s/chip)\n")
        print("| run | value | unit | written (UTC) | source |")
        print("|---|---|---|---|---|")
        print("\n".join(rows))


def tf_rows():
    for f in sorted(glob.glob(os.path.join(RES, "tf_*.out"))):
        try:
            rows = json.loads(open(f).read())
        except (OSError, json.JSONDecodeError):
            continue
        print(f"\n### {os.path.basename(f)} (written {mtime(f)} UTC)\n")
        print("| model | batch | seq | ms/step | tokens/s |")
        print("|---|---|---|---|---|")
        for r in rows:
            print(f"| {r.get('model')} | {r.get('batch')} | {r.get('seq')} "
                  f"| {r.get('ms_per_step')} | {r.get('tokens_per_s')} |")


def pytest_outcomes():
    for f in sorted(glob.glob(os.path.join(RES, "fa_tpu_tests*.out"))):
        try:
            txt = open(f).read()
        except OSError:
            continue
        m = re.search(r"=+ (.*(?:passed|failed|error).*?) =+\s*$", txt,
                      re.M)
        if m:
            print(f"\n### {os.path.basename(f)} (written {mtime(f)} UTC): "
                  f"{m.group(1)}")
        for line in re.findall(r"^(FAILED .*)$", txt, re.M):
            print(f"  - {line}")


def json_files():
    for name in ("conv_summary.json", "autotune_report.json"):
        path = os.path.join(RES, name)
        if os.path.exists(path):
            print(f"\n### {name}\n```json")
            print(open(path).read().strip()[:2000])
            print("```")


def sweeps():
    rows = []
    for f in sorted(glob.glob(os.path.join(RES, "fa_sweep_*.out"))):
        name = os.path.basename(f)[len("fa_sweep_"):-len(".out")]
        try:
            txt = open(f).read()
        except OSError:
            continue
        for line in txt.strip().splitlines():
            if line.startswith("{") or "tokens/s" in line or "ms" in line:
                rows.append(f"| {name} | `{line.strip()[:100]}` |")
    if rows:
        print("\n### FA block sweep (raw lines)\n")
        print("| blocks | line |")
        print("|---|---|")
        print("\n".join(rows))


def offline_ab_rows():
    """The offline AOT evidence (PERF.md §7-§9): one table, latest row
    per tag."""
    path = os.path.join(RES, "offline_ab.jsonl")
    if not os.path.exists(path):
        return
    # Supersession rule lives in _ab_rows (latest line per tag wins;
    # pinned by tests/test_offline_ab_parser.py).
    from _ab_rows import load_rows, superseded_count

    rows = load_rows(path)
    if not rows:
        return
    dropped = superseded_count(open(path).read().strip().splitlines())
    print(f"\n### offline AOT A/Bs ({mtime(path)}; latest row per tag, "
          f"{dropped} superseded row(s) hidden)\n")
    print("| tag | GB/dev | TFLOP/dev | temp GB | resident GB | note |")
    print("|---|---|---|---|---|---|")
    for r in rows:
        tag = r.get("tag", "?")
        if "compile_error" in r:
            print(f"| {tag} | — | — | — | — | "
                  f"ERROR: {r['compile_error'][:60]} |")
            continue
        gb = r.get("gb_per_dev", r.get("gb", ""))
        fl = r.get("flops_per_dev", r.get("flops", 0)) / 1e12
        print(f"| {tag} | {gb} | {fl:.2f} | "
              f"{r.get('temp_gb_per_dev', r.get('temp_gb', ''))} | "
              f"{r.get('resident_gb_per_dev', '')} | "
              f"{'ar=' + str(r['allreduce_payload_mb']) + 'MB' if 'allreduce_payload_mb' in r else ''} |")


def main():
    print("# perf/results summary (generated by perf/summarize_results.py)")
    bench_rows()
    tf_rows()
    pytest_outcomes()
    sweeps()
    offline_ab_rows()
    json_files()


if __name__ == "__main__":
    sys.exit(main())
