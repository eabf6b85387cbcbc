# Parent against change on one chip, in one call: the serving cell traced
# through perf/pr39/validate.py, change then parent, on one seed; then the
# LM training cell's rate, parent A, change A, change B, parent B,
# untraced, through perf/pr38/cell.py.  No reference in either.  The
# parent is `git archive HEAD` unpacked under _bench_proof/parent with
# this tree's BENCHMARK.json and benchmark/ laid over it.
# usage: bash perf/pr39/ab.sh
here=$(pwd)
out=$here/chiprun_out/pr39
mkdir -p "$out"
one() {  # one <dir> <tag> <cell> <seed> <trace> [span]
  SECONDS=0
  f=$out/ab_$2_$3_$4_t$5
  if [ "$5" = 1 ]; then
    (cd "$1" && python3 "$here/perf/pr39/validate.py" --workload "$3" \
       --seed "$4" --seconds 20 --span "$6" --no-reference > "$f.out" 2> "$f.err")
  else
    (cd "$1" && python3 "$here/perf/pr38/cell.py" --workload "$3" \
       --seed "$4" --seconds 20 --trace 0 --no-reference > "$f.out" 2> "$f.err")
  fi
  echo "== $2 $3 seed $4 trace $5 rc=$? after ${SECONDS} s"
  grep -E "set-up done|window closed|Error|error:" "$f.err" | cut -c1-300 | head -n 8
  tail -n 2 "$f.out" | cut -c1-4000
}
serve=lm124m.serve_chat_r80
lm=lm124m.train_b8_s2048
one "$here" change $serve 2147484611 1 engine.decode.fetch
one _bench_proof/parent parent $serve 2147484611 1 engine.decode.fetch
one _bench_proof/parent parent $lm 2147484621 0
one "$here" change $lm 2147484621 0
one "$here" change $lm 2147484622 0
one _bench_proof/parent parent $lm 2147484622 0
