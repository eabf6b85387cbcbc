# usage: bash perf/pr34/run_cell.sh <tag> <workload> <trace> <seed>...
# One run a seed, result lines under chiprun_out/pr34/<tag>_<seed>.json.
tag=$1; cell=$2; trace=$3; shift 3
mkdir -p chiprun_out/pr34
for seed in "$@"; do
  out=chiprun_out/pr34/${tag}_${seed}
  python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds 20 --trace "$trace" > "$out.json" 2> "$out.err"
  echo "== $tag seed $seed rc=$?"
  grep -E "harness built|weights from|step 1 done|set-up done|memory_stats|reference followed|^compared|kernel " "$out.err" "$out.json" | cut -c1-260
  tail -n 1 "$out.json" | cut -c1-2500
done
