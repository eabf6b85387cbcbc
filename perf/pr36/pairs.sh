# parent (git archive of 4f1da88 under _bench_proof/parent) against the change
# on one chip, in the order parent, change, change, parent; the change's first
# run of a cell is traced where <trace> is 1 (the loader's spans).
# usage: bash perf/pr36/pairs.sh <change dir> <tag> <cell> <trace> <seed a> <seed b>
mkdir -p chiprun_out/pr36
here=$(pwd)
run() {  # run <dir> <side> <cell> <trace> <seed>
  out="$here/chiprun_out/pr36/$tag.$3.$2.$5"
  (cd "$1" && python3 benchmark/run.py --workload "$3" --seed "$5" --seconds 20 --trace "$4" > "$out.json" 2> "$out.err")
  echo "== $2 $3 trace $4 seed $5 rc=$?"; tail -n 1 "$out.json" | cut -c1-2600
}
change=$1; tag=$2; cell=$3; trace=$4
run _bench_proof/parent parent "$cell" 0 "$5"
run "$change" change "$cell" "$trace" "$5"
run "$change" change "$cell" 0 "$6"
run _bench_proof/parent parent "$cell" 0 "$6"
