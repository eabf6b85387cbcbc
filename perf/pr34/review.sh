# after the review: the stalled seed watched span by span four times, then
# post-norm gains of 1 against 0.02 on the same seeds in one process.
mkdir -p chiprun_out/pr34
for i in 1 2 3 4; do
  python3 perf/pr34/window_spans.py --seed 34105 2> chiprun_out/pr34/ws2_$i.err | tail -n 1 | tee chiprun_out/pr34/ws2_$i.out | cut -c1-1500
  echo "== ws2 $i rc=$?"
done
python3 perf/pr34/gain.py --seeds 34601 34602 34603 2147487001 2147487002 2> chiprun_out/pr34/gain.err | tee chiprun_out/pr34/gain.out
echo "== gain rc=$?"; tail -n 5 chiprun_out/pr34/gain.err | cut -c1-400
