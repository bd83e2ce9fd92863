# PR 54, the second call (1.6/s, requests measured by their due instant: its
# outputs are under set_by_due_instant/): the cell at its rate, from the files as they
# stand. Six untraced runs a seed of its own each (the spread of
# serve_ttft_p95_ms and setup_s against half their bounds), the traced run,
# and the same rate with reuse switched off IN THIS RECORD (sweep.py
# --no-reuse wraps RadixTree.match to find nothing).
#   chiprun --chips 1 --timeout 3600 -- sh chipbench/records/sarvam-105b/call2.sh
out=chiprun_out/sarvam; mkdir -p $out/setA
cell=sarvam-105b.serve-doc-sessions
# the knee once more on another seed, 51 s windows, at the pages the cell now has
python3 chipbench/records/sarvam-105b/sweep.py --rates 1.6,1.6,2.0,2.5 --seconds 51 --seed 11 > $out/sweep_seed11.log 2> $out/sweep_seed11.err
echo sweep11_rc=$?; grep sweep $out/sweep_seed11.log | cut -c1-700
rate=$(python3 -c "import json; print(json.load(open('chipbench/traffic/serve-doc-sessions.json'))['arrivals']['rate_per_s'])")
for seed in 2147483801 2147483802 2147483803 2147483804 2147483805 2147483806; do
  python3 -m chipbench.run --workload $cell --seed $seed --seconds 51 --trace 0 > $out/setA/$seed.log 2> $out/setA/$seed.err
  echo seed=$seed rc=$?; grep '"event": "check"' $out/setA/$seed.log | cut -c1-1800; grep '"event": "sweep"' $out/setA/$seed.log | cut -c1-600; tail -n 1 $out/setA/$seed.log | cut -c1-500
done
python3 -m chipbench.run --workload $cell --seed 2147483811 --seconds 51 --trace 1 > $out/traced_2147483811.log 2> $out/traced_2147483811.err
echo traced_rc=$?; grep '"event": "unread"' $out/traced_2147483811.log; tail -n 1 $out/traced_2147483811.log | cut -c1-6000; tail -n 3 $out/traced_2147483811.err | cut -c1-300
python3 chipbench/records/sarvam-105b/sweep.py --rates $rate,$rate --seconds 51 --seed 2147483821 > $out/reuse_on_at_rate.log 2> $out/reuse_on_at_rate.err
echo reuse_on_rc=$?; grep sweep $out/reuse_on_at_rate.log | cut -c1-1200
python3 chipbench/records/sarvam-105b/sweep.py --rates $rate,$rate --seconds 51 --seed 2147483821 --no-reuse > $out/reuse_off_at_rate.log 2> $out/reuse_off_at_rate.err
echo reuse_off_rc=$?; grep sweep $out/reuse_off_at_rate.log | cut -c1-1200
