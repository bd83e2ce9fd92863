# PR 54, the third call: the second call again after its findings (the asks of
# the window's documents measured, 1.28/s, the cold program under its name). Six untraced runs a seed of its own each (the spread of
# serve_ttft_p95_ms and setup_s against half their bounds), the traced run,
# and the same rate with reuse switched off IN THIS RECORD (sweep.py
# --no-reuse wraps RadixTree.match to find nothing).
#   chiprun --chips 1 --timeout 3600 -- sh chipbench/records/sarvam-105b/call3.sh
out=chiprun_out/sarvam; mkdir -p $out/setB
cell=sarvam-105b.serve-doc-sessions
rate=$(python3 -c "import json; print(json.load(open('chipbench/traffic/serve-doc-sessions.json'))['arrivals']['rate_per_s'])")
for seed in 2147484001 2147484002 2147484003 2147484004 2147484005 2147484006; do
  python3 -m chipbench.run --workload $cell --seed $seed --seconds 51 --trace 0 > $out/setB/$seed.log 2> $out/setB/$seed.err
  echo seed=$seed rc=$?; grep '"event": "check"' $out/setB/$seed.log | cut -c1-1800; grep '"event": "sweep"' $out/setB/$seed.log | cut -c1-600; tail -n 1 $out/setB/$seed.log | cut -c1-500
done
python3 -m chipbench.run --workload $cell --seed 2147484011 --seconds 51 --trace 1 > $out/traced_2147484011.log 2> $out/traced_2147484011.err
echo traced_rc=$?; grep '"event": "unread"' $out/traced_2147484011.log; tail -n 1 $out/traced_2147484011.log | cut -c1-6000; tail -n 3 $out/traced_2147484011.err | cut -c1-300
python3 chipbench/records/sarvam-105b/sweep.py --rates $rate,$rate --seconds 51 --seed 2147484021 > $out/reuse_on_at_rate.log 2> $out/reuse_on_at_rate.err
echo reuse_on_rc=$?; grep sweep $out/reuse_on_at_rate.log | cut -c1-1200
python3 chipbench/records/sarvam-105b/sweep.py --rates $rate,$rate --seconds 51 --seed 2147484021 --no-reuse > $out/reuse_off_at_rate.log 2> $out/reuse_off_at_rate.err
echo reuse_off_rc=$?; grep sweep $out/reuse_off_at_rate.log | cut -c1-1200
