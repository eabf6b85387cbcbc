# the committed files alone (git archive $(git write-tree) under
# _archive_proof/change).  usage: bash perf/pr34/final.sh [pairs]
mkdir -p chiprun_out/pr34
here=$(pwd)
run() {  # run <dir> <tag> <cell> <trace> <seed>
  (cd "$1" && python3 benchmark/run.py --workload "$3" --seed "$5" --seconds 20 --trace "$4" > "$here/chiprun_out/pr34/$2_$5.json" 2> "$here/chiprun_out/pr34/$2_$5.err")
  echo "== $2 $3 trace $4 seed $5 rc=$?"; tail -n 1 "chiprun_out/pr34/$2_$5.json" | cut -c1-1800
}
if [ "$1" = pairs ]; then  # call 49: the new cell and the ResNet pairs
  run _archive_proof/change arch_t trinity_mini.train_b1_s8192 1 2147485001
  run _archive_proof/change arch_u trinity_mini.train_b1_s8192 0 34402
  run _bench_proof/parent rn_parent resnet50.train_b256 0 34403
  run _archive_proof/change rn_change resnet50.train_b256 0 34403
  run _archive_proof/change rn_change resnet50.train_b256 0 34404
  run _bench_proof/parent rn_parent resnet50.train_b256 0 34404
else  # after the review: the new cell under its new limits, traced and four untraced
  run _archive_proof/change fin_t trinity_mini.train_b1_s8192 1 2147488001
  for seed in 34701 34702 2147488002 2147488003; do
    run _archive_proof/change fin_u trinity_mini.train_b1_s8192 0 $seed
  done
fi
