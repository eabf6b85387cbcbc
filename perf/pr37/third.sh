# call 3 (the working tree): the new cell traced with its routing counters,
# the int8 control and the half-sequence fault on three seeds, then set A:
# five untraced runs.
mkdir -p chiprun_out/pr37
out=chiprun_out/pr37/third_t
SECONDS=0
JAX_DEBUG_LOG_MODULES=jax._src.compiler,jax._src.compilation_cache \
  python3 perf/pr37/with_counters.py --workload kanana2_30b_a3b.train_b1_s8192 --seed 2147485001 --seconds 20 --trace 1 > $out.json 2> $out.err
echo "== traced with counters rc=$? after ${SECONDS} s"
grep -E "bench \+|^compared|grad_norms" $out.err | cut -c1-400
grep -iE "persistent|cache (hit|miss)|not writing|writing .*cache" $out.err | cut -c1-200 | sort | uniq -c | sort -rn | head -n 30
du -sh ${JAX_COMPILATION_CACHE_DIR:-.xla_cache}; ls -laS ${JAX_COMPILATION_CACHE_DIR:-.xla_cache} | head -n 12
tail -n 2 $out.json | cut -c1-3500
python3 benchmark/tests/readings_on_chip.py --workload kanana2_30b_a3b.train_b1_s8192 \
  --seeds 37011,2147484911,37012 > chiprun_out/pr37/readings3.jsonl 2> chiprun_out/pr37/readings3.err
echo "== readings rc=$? after ${SECONDS} s"; cut -c1-2500 chiprun_out/pr37/readings3.jsonl
bash perf/pr37/run_cell.sh set_a kanana2_30b_a3b.train_b1_s8192 0 37021 2147485002 37022 2147485003 37023 | grep -v "kernel \|memory_stats\|harness built"
echo "== all after ${SECONDS} s"
