# The training step's cost, parent against change, on one chip in one
# call: for each cell given, parent A, change A, change B, parent B, each
# untraced and without the reference (rate only), through perf/pr38/cell.py.
# The parent is `git archive HEAD` unpacked under _bench_proof/parent with
# this tree's BENCHMARK.json and benchmark/ laid over it, made before the
# call (the chip machine has no .git).
# usage: bash perf/pr39/lm_pair.sh <seed A> <seed B> <cell>...
here=$(pwd)
out=$here/chiprun_out/pr39
mkdir -p "$out"
a=$1; b=$2; shift 2
one() {  # one <dir> <tag> <cell> <seed>
  SECONDS=0
  (cd "$1" && python3 "$here/perf/pr38/cell.py" --workload "$3" --seed "$4" \
     --seconds 20 --trace 0 --no-reference \
     > "$out/pair_$2_$3_$4.json" 2> "$out/pair_$2_$3_$4.err")
  echo "== $2 $3 seed $4 rc=$? after ${SECONDS} s"
  grep -E "set-up done|window closed|Error|error:" "$out/pair_$2_$3_$4.err" \
    | cut -c1-300 | head -n 8
  tail -n 1 "$out/pair_$2_$3_$4.json" | cut -c1-2500
}
for cell in "$@"; do
  one _bench_proof/parent parent "$cell" "$a"
  one "$here" change "$cell" "$a"
  one "$here" change "$cell" "$b"
  one _bench_proof/parent parent "$cell" "$b"
done
