# After the driver's first check could not tell (`serve_tpot_p50_ms` in
# gpt2-125m.serve-chat: the middle half of six runs spread 0.031 ms at the
# parent, 0.081 ms with the change, bound 0.039 ms): untraced pairs of that
# cell alone, parent and change in turn on ONE machine from ONE path, every
# pair on a seed of its own, which side goes first alternating (runs.sh).
#   git add -A; sh chipbench/records/serve-waits/prepare.sh <parent commit> index    (here)
#   chiprun --chips 1 --timeout 2700 -- sh chipbench/records/serve-waits/steady.sh <tag> <base seed> <pairs> [2: through timed_steps.py]
# With a third tree .bench_check/held (the change as the driver first measured
# it: the inputs of a step held in locals until `engine.decode` returns) every
# seed runs all three.
# steady.py reads the runs' logs and prints each side's spread as the driver
# computes it.
out=$PWD/chiprun_out/pr42/$1; inside=
. "$(dirname "$0")/runs.sh"
if [ -d .bench_check/held ]; then   # a third tree beside the two: each seed runs all three, the order turning
  i=1
  while [ $i -le $3 ]; do
    case $((i % 3)) in 0) order="held change parent";; 1) order="parent held change";; 2) order="change parent held";; esac
    for side in $order; do run $side gpt2-125m.serve-chat $(($2 + i)) ${4:-0} $side.$i; done
    i=$((i + 1))
  done
else pairs gpt2-125m.serve-chat $2 1 $3 $4; fi
done_runs
