# PR 45: the sweep on the second seed (first pass discarded), then six seeds
# of the cell at the rate its traffic file gives, a process each.
#   RATES=... chiprun --chips 1 --timeout 3500 -- sh chipbench/records/kimi-linear/seeds.sh <set> <seed0>
out=chiprun_out/kimi-linear; mkdir -p $out
cell=kimi-linear-48b-a3b.serve-long-answer
if [ -n "$RATES" ]; then
  t0=$(date +%s)
  python3 -m chipbench.tools.sweep --workload $cell --rates $RATES --seconds 30 --seed 11 > $out/sweep_seed11.log 2> $out/sweep_seed11.err
  echo "sweep rc=$? in $(( $(date +%s) - t0 )) s"; cut -c1-700 $out/sweep_seed11.log; tail -c 1000 $out/sweep_seed11.err
fi
grep -n '"rate_per_s"' chipbench/traffic/serve-long-answer.json
python3 -m chipbench.tools.repeat --workload $cell --runs ${RUNS:-6} --seconds 51 --seed0 ${2:-2147485045} --out $out/${1:-setA} 2>&1 | cut -c1-420
for f in $out/${1:-setA}/$cell.*.log; do grep -h '"event": "check"\|"event": "sweep"' $f | cut -c1-900; done
