# One `--trace 1` run of every cell on this tree through
# perf/pr39/validate.py, without the reference: the ring's device
# intervals beside the same run's profiler trace.  Serving's ring spans
# are matched to the trace by engine.decode.fetch, training's by
# train.dispatch.
# usage: bash perf/pr39/first.sh
here=$(pwd)
out=$here/chiprun_out/pr39
mkdir -p "$out"
one() {  # one <cell> <seed> <span>
  SECONDS=0
  python3 "$here/perf/pr39/validate.py" --workload "$1" --seed "$2" \
    --seconds 20 --span "$3" --no-reference > "$out/$1_$2.out" 2> "$out/$1_$2.err"
  echo "== $1 seed $2 rc=$? after ${SECONDS} s"
  grep -E "set-up done|window closed|memory_stats|Error|error:" "$out/$1_$2.err" \
    | cut -c1-300 | head -n 8
  tail -n 2 "$out/$1_$2.out" | cut -c1-4000
}
one lm124m.serve_chat_r80 2147484601 engine.decode.fetch
one lm124m.train_b8_s2048 2147484602 train.dispatch
one resnet50.train_b256 2147484603 train.dispatch
one trinity_mini.train_b1_s8192 2147484604 train.dispatch
one kanana2_30b_a3b.train_b1_s8192 2147484605 train.dispatch
