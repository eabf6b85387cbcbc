# the committed files alone: `git archive $(git write-tree)` under
# _archive_proof/change against the parent under _bench_proof/parent.
# usage: bash perf/pr36/final.sh resnet|others
here=$(pwd)
mkdir -p chiprun_out/pr36
pairs() { bash perf/pr36/pairs.sh _archive_proof/change arch "$@"; }
one() {  # one <dir> <side> <cell> <trace> <seed>: a single run, pairs.sh's naming
  out="$here/chiprun_out/pr36/arch.$3.$2.$5"
  (cd "$1" && python3 benchmark/run.py --workload "$3" --seed "$5" --seconds 20 --trace "$4" > "$out.json" 2> "$out.err")
  echo "== $2 $3 trace $4 seed $5 rc=$?"; tail -n 1 "$out.json" | cut -c1-2600
}
if [ "$1" = resnet ]; then
  for side in _archive_proof/change _bench_proof/parent; do
    (cd $side && python3 "$here/perf/pr36/cast_span.py" 2> "$here/chiprun_out/pr36/cast_span.$(basename $side).err" | tail -n 1 | tee "$here/chiprun_out/pr36/cast_span.$(basename $side).json")
  done
  pairs resnet50.train_b256 0 36201 2147483736
  pairs resnet50.train_b256 0 2147483836 36202
  one _archive_proof/change change resnet50.train_b256 1 2147483936
else
  one _bench_proof/parent parent lm124m.train_b8_s2048 0 2147484036
  one _archive_proof/change change lm124m.train_b8_s2048 0 2147484036
  one _archive_proof/change change trinity_mini.train_b1_s8192 0 36301
  one _bench_proof/parent parent trinity_mini.train_b1_s8192 0 36301
  pairs lm124m.serve_chat_r80 0 36401 2147484136
fi
