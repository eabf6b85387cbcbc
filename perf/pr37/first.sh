# call 1: the kernel sweep, then the new cell once, traced, on the working tree
mkdir -p chiprun_out/pr37
python perf/pr37/kernel_sweep.py > chiprun_out/pr37/sweep.jsonl 2> chiprun_out/pr37/sweep.err
echo "== sweep rc=$?"; cat chiprun_out/pr37/sweep.jsonl; tail -n 5 chiprun_out/pr37/sweep.err | cut -c1-300
bash perf/pr37/run_cell.sh first kanana2_30b_a3b.train_b1_s8192 1 2147483777
