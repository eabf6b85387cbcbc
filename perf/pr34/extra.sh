# a reading PERF.md cites and the benchmark does not keep: the issue's
# first cut (16 experts held: _archive_proof/e16, the configuration file
# edited in a copy) on one chip.
# usage: bash perf/pr34/extra.sh <seed>
mkdir -p chiprun_out/pr34
here=$(pwd)
seed=$1
(cd _archive_proof/e16 && python3 benchmark/run.py --workload trinity_mini.train_b1_s8192 --seed "$seed" --seconds 20 --trace 0 > "$here/chiprun_out/pr34/e16_$seed.json" 2> "$here/chiprun_out/pr34/e16_$seed.err")
echo "== e16 rc=$?"; grep -i "bench +\|RESOURCE_EXHAUSTED\|Error" chiprun_out/pr34/e16_$seed.err | tail -n 8 | cut -c1-300; tail -n 1 chiprun_out/pr34/e16_$seed.json | cut -c1-600
