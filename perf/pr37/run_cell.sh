# usage: bash perf/pr37/run_cell.sh <tag> <workload> <trace> <seed>...
# One run a seed from the directory it is called in; result lines under
# chiprun_out/pr37/<tag>_<seed>.json.  OUT overrides the output directory
# (for a run from an unpacked archive).
tag=$1; cell=$2; trace=$3; shift 3
out_dir=${OUT:-chiprun_out/pr37}
mkdir -p "$out_dir"
for seed in "$@"; do
  out=$out_dir/${tag}_${seed}
  SECONDS=0
  python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds 20 --trace "$trace" > "$out.json" 2> "$out.err"
  echo "== $tag $cell trace $trace seed $seed rc=$? after ${SECONDS} s"
  grep -E "harness built|set-up done|memory_stats|reference followed|^compared|kernel |grad_norms|Error|error:" "$out.err" "$out.json" | cut -c1-400 | head -n 40
  tail -n 1 "$out.json" | cut -c1-3000
done
