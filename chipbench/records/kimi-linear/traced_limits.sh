# PR 45: one traced run with the breakdown (where a 36 ms decode step goes),
# then the readings the family's limits lie between.
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/records/kimi-linear/traced_limits.sh
out=chiprun_out/kimi-linear; mkdir -p $out
cell=kimi-linear-48b-a3b.serve-long-answer
t0=$(date +%s)
python3 -m chipbench.run --workload $cell --seed 2147400045 --seconds 51 --trace 1 > $out/traced.log 2> $out/traced.err
echo "traced rc=$? in $(( $(date +%s) - t0 )) s; unread lines: $(grep -c unread $out/traced.log)"; tail -n 1 $out/traced.log | cut -c1-7000; tail -c 1500 $out/traced.err
t0=$(date +%s)
python3 -m chipbench.tools.check_limits_knobs --workload $cell --seed ${SEED:-2147483745} --seconds 20 > $out/limits_readings.log 2> $out/limits_readings.err
echo "limits rc=$? in $(( $(date +%s) - t0 )) s"; cut -c1-1800 $out/limits_readings.log; tail -c 1500 $out/limits_readings.err
