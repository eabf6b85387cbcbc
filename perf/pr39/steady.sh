# The serving cell's steadiness, parent against change, on one chip in one
# call: six seeds, each run once on the parent and once on the change, in
# the order P C C P P C C P P C C P, untraced and without the reference
# (rate only), through perf/pr38/cell.py.  Then, for each side and each
# end-to-end metric, the median and the quartile spread of its runs, as a
# share of the median, beside that spread with the run farthest from the
# median left out.
# The parent is `git archive HEAD` unpacked under _bench_proof/parent with
# this tree's BENCHMARK.json and benchmark/ laid over it, made before the
# call (the chip machine has no .git).
# usage: bash perf/pr39/steady.sh <first seed>
here=$(pwd)
out=$here/chiprun_out/pr39/steady
mkdir -p "$out"
s=$1
one() {  # one <dir> <tag> <seed>
  SECONDS=0
  (cd "$1" && python3 "$here/perf/pr38/cell.py" \
     --workload lm124m.serve_chat_r80 --seed "$3" --seconds 20 --trace 0 \
     --no-reference > "$out/$2_$3.json" 2> "$out/$2_$3.err")
  echo "== $2 seed $3 rc=$? after ${SECONDS} s"
  grep -E "Error|error:" "$out/$2_$3.err" | cut -c1-300 | head -n 4
}
for i in 0 1 2; do
  a=$((s + 2 * i)); b=$((s + 2 * i + 1))
  one _bench_proof/parent parent $a
  one "$here" change $a
  one "$here" change $b
  one _bench_proof/parent parent $b
done
python3 - "$out" <<'EOF'
import glob, json, os, statistics, sys
names = ("serve_ttft_p95_ms", "serve_tpot_p95_ms", "serve_tok_per_s",
         "setup_s")
for side in ("parent", "change"):
    runs = {}
    for f in sorted(glob.glob(os.path.join(sys.argv[1], side + "_*.json"))):
        lines = open(f).read().split("\n")
        try:
            res = json.loads([l for l in lines if l.strip()][-1])
        except (IndexError, ValueError):
            continue
        m = res.get("metrics", {})
        runs[os.path.basename(f)] = {k: m[k]["value"] for k in names
                                     if k in m}
    print(side, json.dumps(runs))
    for k in names:
        v = sorted(r[k] for r in runs.values() if k in r)
        if len(v) < 4:
            continue
        med = statistics.median(v)
        q = statistics.quantiles(v, n=4)
        w = sorted(v, key=lambda x: abs(x - med))[:-1]
        qw = statistics.quantiles(w, n=4)
        print(f"  {k}: median {med:.6g}  iqr {q[2] - q[0]:.6g} "
              f"({100 * (q[2] - q[0]) / med:.3g}%)  without the farthest "
              f"{qw[2] - qw[0]:.6g} ({100 * (qw[2] - qw[0]) / med:.3g}%)")
EOF
