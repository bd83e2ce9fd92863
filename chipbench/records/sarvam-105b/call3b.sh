# PR 54, the third call again (call 3b: a session's asks turn with their document; outputs setC): (the asks of
# the window's documents measured, 1.28/s, the cold program under its name). Six untraced runs a seed of its own each (the spread of
# serve_ttft_p95_ms and setup_s against half their bounds), the traced run,
# --no-reuse wraps RadixTree.match to find nothing).
#   chiprun --chips 1 --timeout 3600 -- sh chipbench/records/sarvam-105b/call3.sh
out=chiprun_out/sarvam; mkdir -p $out/setC
cell=sarvam-105b.serve-doc-sessions
rate=$(python3 -c "import json; print(json.load(open('chipbench/traffic/serve-doc-sessions.json'))['arrivals']['rate_per_s'])")
for seed in 2147484101 2147484102 2147484103 2147484104 2147484105 2147484106; do
  python3 -m chipbench.run --workload $cell --seed $seed --seconds 51 --trace 0 > $out/setC/$seed.log 2> $out/setC/$seed.err
  echo seed=$seed rc=$?; grep '"event": "check"' $out/setC/$seed.log | cut -c1-1800; grep '"event": "sweep"' $out/setC/$seed.log | cut -c1-600; tail -n 1 $out/setC/$seed.log | cut -c1-500
done
python3 -m chipbench.run --workload $cell --seed 2147484111 --seconds 51 --trace 1 > $out/traced_2147484111.log 2> $out/traced_2147484111.err
echo traced_rc=$?; grep '"event": "unread"' $out/traced_2147484111.log; tail -n 1 $out/traced_2147484111.log | cut -c1-6000; tail -n 3 $out/traced_2147484111.err | cut -c1-300
