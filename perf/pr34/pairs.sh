# parent (unpacked under _bench_proof/parent with this PR's benchmark files
# laid over it) against the change on one chip: the new cell must fail at
# once on the parent; lm124m.train_b8_s2048 in the order parent, change,
# change, parent.
mkdir -p chiprun_out/pr34
here=$(pwd)
cd _bench_proof/parent
SECONDS=0
python3 benchmark/run.py --workload trinity_mini.train_b1_s8192 --seed 7 --seconds 20 --trace 0 > "$here/chiprun_out/pr34/parent_trinity.json" 2> "$here/chiprun_out/pr34/parent_trinity.err"
echo "== parent on the new cell: rc=$? after ${SECONDS} s"; tail -n 2 "$here/chiprun_out/pr34/parent_trinity.err" | cut -c1-300
cd "$here"
run() {  # run <dir> <tag> <seed>
  (cd "$1" && python3 benchmark/run.py --workload lm124m.train_b8_s2048 --seed "$3" --seconds 20 --trace 0 > "$here/chiprun_out/pr34/lm_$2_$3.json" 2> "$here/chiprun_out/pr34/lm_$2_$3.err")
  echo "== lm124m $2 seed $3 rc=$?"; tail -n 1 "chiprun_out/pr34/lm_$2_$3.json" | cut -c1-900
}
run _bench_proof/parent parent "$1"
run . change "$1"
run . change "$2"
run _bench_proof/parent parent "$2"
