# PR 49: the knee again below 1.6/s, where the first sweep (first.sh) showed
# the queue already growing: 60 s windows, two seeds, a process a seed, the
# first rate of each twice (a process's first pass is discarded).
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/records/mimo-v2.5/sweeps.sh <rates seed 7> <rates seed 11>
out=chiprun_out/mimo-v2.5; mkdir -p $out
cell=mimo-v2.5.serve-code-agent
for pair in "7 $1" "11 $2"; do
  set -- $pair
  t0=$(date +%s)
  python3 -m chipbench.tools.sweep --workload $cell --rates $2 --seconds 60 --seed $1 > $out/sweep_seed$1_low.log 2> $out/sweep_seed$1_low.err
  echo "sweep seed $1 rc=$? in $(( $(date +%s) - t0 )) s"; cut -c1-700 $out/sweep_seed$1_low.log; tail -c 600 $out/sweep_seed$1_low.err
done
