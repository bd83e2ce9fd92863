# PR 41: cells whose programs run code this PR changed, parent commit against
# change, on one machine: parent, change, change, parent; the two runs of a
# pair share a seed. .bench_check holds `git archive` of the parent commit with
# this PR's BENCHMARK.json and chipbench/ laid over it (made before the call).
#   chiprun --chips 1 --timeout 3500 -- sh chipbench/records/k-exaone-compact/pairs.sh <cell> ...
out=$PWD/chiprun_out/pr41/pairs; mkdir -p $out
base=2147441000
for cell in "$@"; do
  base=$((base + 1000003))
  for run in parent:1 change:1 change:2 parent:2; do
    side=${run%:*}; seed=$((base + ${run#*:}))
    case $side in parent) dir=.bench_check;; change) dir=.;; esac
    ( cd $dir; python3 -m chipbench.run --workload $cell --seed $seed --seconds 51 --trace 0 > $out/$cell.$side.$seed.log 2> $out/$cell.$side.$seed.err )
    echo "$cell $side seed $seed rc=$?: $(tail -n 1 $out/$cell.$side.$seed.log | cut -c1-420)"
  done
done
