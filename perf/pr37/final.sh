# the committed files alone: `git archive $(git write-tree)` unpacked under
# _archive_proof/change; the parent (18e19c5) under _bench_proof/parent with
# this PR's BENCHMARK.json and benchmark/ laid over it.
# usage: bash perf/pr37/final.sh cell | lm | trinity | others
here=$(pwd)
export OUT=$here/chiprun_out/pr37
mkdir -p "$OUT"
run() {  # run <dir> <tag> <cell> <trace> <seed>...
  dir=$1; shift
  (cd "$dir" && bash "$here/perf/pr37/run_cell.sh" "$@" | grep -v "kernel \|memory_stats\|harness built")
}
new=kanana2_30b_a3b.train_b1_s8192
case $1 in
cell)   # the parent fails at once; the change traced (with its routing
        # counters), then set A and set B untraced, as many as the time allows
  SECONDS=0
  (cd _bench_proof/parent && python3 benchmark/run.py --workload $new --seed 7 --seconds 20 --trace 0 > "$OUT/parent_new_cell.json" 2> "$OUT/parent_new_cell.err")
  echo "== parent on the new cell: rc=$? after ${SECONDS} s"; tail -n 2 "$OUT/parent_new_cell.err" | cut -c1-300
  (cd _archive_proof/change && python3 perf/pr37/with_counters.py --workload $new --seed 2147485101 --seconds 20 --trace 1 > "$OUT/fin_t.json" 2> "$OUT/fin_t.err")
  echo "== traced with counters rc=$? after ${SECONDS} s"; grep -E "set-up done|reference followed|^compared" "$OUT/fin_t.err" | cut -c1-300; tail -n 2 "$OUT/fin_t.json" | cut -c1-3000
  run _archive_proof/change fin_a $new 0 37101 2147485102 37102 2147485103 37103
  run _archive_proof/change fin_b $new 0 2147485104 37104 2147485105 37105 2147485106
  ;;
lm)
  run _bench_proof/parent lm_parent lm124m.train_b8_s2048 0 37201
  run _archive_proof/change lm_change lm124m.train_b8_s2048 0 37201 2147485201
  run _bench_proof/parent lm_parent lm124m.train_b8_s2048 0 2147485201
  ;;
trinity)
  run _bench_proof/parent tr_parent trinity_mini.train_b1_s8192 0 37301
  run _archive_proof/change tr_change trinity_mini.train_b1_s8192 0 37301 2147485301
  run _bench_proof/parent tr_parent trinity_mini.train_b1_s8192 0 2147485301
  ;;
others)
  run _bench_proof/parent rn_parent resnet50.train_b256 0 37401
  run _archive_proof/change rn_change resnet50.train_b256 0 37401
  run _archive_proof/change sv_change lm124m.serve_chat_r80 0 37501
  run _bench_proof/parent sv_parent lm124m.serve_chat_r80 0 37501
  ;;
esac
