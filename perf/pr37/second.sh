# call 2: the new cell traced and twice untraced on the working tree, then
# the readings its limits are set from: five more program seeds in one
# process, and the int8 control and the half-sequence fault on three seeds.
mkdir -p chiprun_out/pr37
bash perf/pr37/run_cell.sh second kanana2_30b_a3b.train_b1_s8192 1 2147483777
bash perf/pr37/run_cell.sh second kanana2_30b_a3b.train_b1_s8192 0 37001 2147484901
python3 benchmark/tests/readings_on_chip.py --workload kanana2_30b_a3b.train_b1_s8192 \
  --program-seeds 37002,37003,2147484902,2147484903,37004 --seeds 37011,2147484911,37012 \
  > chiprun_out/pr37/readings.jsonl 2> chiprun_out/pr37/readings.err
echo "== readings rc=$?"; cut -c1-1500 chiprun_out/pr37/readings.jsonl; tail -n 3 chiprun_out/pr37/readings.err | cut -c1-300
