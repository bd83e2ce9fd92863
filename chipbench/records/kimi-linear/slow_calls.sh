# PR 45 after review: one of fourteen untraced runs read serve_ttft_p95_ms 638 ms
# (seed 2153486064, final_review/) where thirteen read 237-247. The same seed
# twice and four new ones under slow_calls.py: does it come back with the seed
# (the traffic) or not (the machine), and what was the run waiting for?
#   chiprun --chips 1 --timeout 1800 -- sh chipbench/records/kimi-linear/slow_calls.sh
# (a second call with OUT=slow_calls2 and six new SEEDS, after slow_calls.py learned
# to read what a slow call's time was spent on)
out=$PWD/chiprun_out/kimi-linear/${OUT:-slow_calls}; mkdir -p $out
cell=kimi-linear-48b-a3b.serve-long-answer
n=0
for seed in ${SEEDS:-2153486064 2154487067 2153486064 2155488073 2156489079 2157490081}; do
  n=$((n + 1)); t0=$(date +%s)
  python3 chipbench/records/kimi-linear/slow_calls.py $out/calls$n.json --workload $cell --seed $seed --seconds 51 > $out/run$n.log 2> $out/run$n.err
  echo "run $n seed $seed: rc=$? in $(( $(date +%s) - t0 )) s"
  grep -h '"event": "sweep"' $out/run$n.log | cut -c1-600; tail -n 1 $out/run$n.log | cut -c1-330
  python3 -c "
import json; r = json.load(open('$out/calls$n.json'))
print({k: (v['n'], round(v['median_ms'], 1), round(v['max_ms'], 1)) for k, v in r['calls'].items()})
print('slow: at_s, kind, active, ms, thread on cpu, process on cpus, thread waiting, machine stolen, blocked, preempted', [(round(c['at_s'], 2), c['kind'], c['n']) + tuple('%.0f' % c.get(k, -1) for k in ('ms', 'thread_cpu_ms', 'process_cpu_ms', 'thread_waited_ms', 'machine_stolen_ms', 'switches_blocked', 'switches_preempted')) for c in r['slow_calls']][:12])
print('gaps', [(round(g['at_s'], 2), round(g['ms']), g['before']) for g in r['gaps_over_20_ms']][:12], 'gc', r['collections'], [(round(c['at_s'], 2), round(c['ms']), c['generation']) for c in r['collections_over_5_ms']][:8])"
done
