# PR 49: sets of the cell at the rate its traffic file gives, a process a
# run, a seed a run; then traced runs.
#   chiprun --chips 1 --timeout 3550 -- sh chipbench/records/mimo-v2.5/sets.sh <set> <seed0> <runs> [traced seeds ...]
# FROM=.archive_check runs the files git would commit (`git archive
# $(git write-tree)` unpacked there before the call).
out=$PWD/chiprun_out/mimo-v2.5; mkdir -p $out
cd ${FROM:-.}
cell=mimo-v2.5.serve-code-agent
set=$1; seed0=$2; runs=$3; shift 3
grep -n '"rate_per_s"\|"n_slots"' chipbench/traffic/serve-code-agent.json
if [ "$runs" -gt 0 ]; then
  python3 -m chipbench.tools.repeat --workload $cell --runs $runs --seconds 51 --seed0 $seed0 --out $out/$set 2>&1 | cut -c1-420
  for f in $out/$set/$cell.*.log; do grep -h '"event": "check"\|"event": "sweep"\|"event": "setup"' $f | cut -c1-1100; done
fi
for seed in "$@"; do
  t0=$(date +%s)
  python3 -m chipbench.run --workload $cell --seed $seed --seconds 51 --trace 1 > $out/traced_$seed.log 2> $out/traced_$seed.err
  echo "traced $seed rc=$? in $(( $(date +%s) - t0 )) s; unread lines: $(grep -c unread $out/traced_$seed.log)"; tail -n 1 $out/traced_$seed.log | cut -c1-6000; tail -c 600 $out/traced_$seed.err
done
