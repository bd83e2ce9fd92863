# PR 51: the cells that run the changed loops, parent (.parent/: `git archive`
# of the parent commit with this PR's BENCHMARK.json and chipbench/ laid over
# it, as the driver's check does) and change (.archive_check/: `git archive
# $(git write-tree)`, the committed files alone) in turn on one machine, the
# two sides of a pair on one seed, no two pairs on one; who goes first
# alternates. A cell's first pair compiles (both sides cold), the others
# load; TRACED=<cell> gives that cell a traced pair after its first.
#   chiprun --chips 1 --timeout 3550 -- env TRACED=<cell> sh chipbench/records/prefill-real-chunks/pairs.sh <first seed> <cell>=<pairs> [<cell>=<pairs> ...]
# (The environment goes through `env`: the chip tool does not forward its
# caller's.)
seed=$1; shift
out=$PWD/chiprun_out/prefill-real-chunks/pairs; mkdir -p $out
run() {  # tree, cell, label, seed, trace
  if [ $1 = parent ]; then dir=.parent; else dir=.archive_check; fi
  t0=$(date +%s)
  (cd $dir && python3 -m chipbench.run --workload $2 --seed $4 --seconds 51 --trace $5 \
     > $out/$2.$3.$1.txt 2>$out/$2.$3.$1.err)
  echo "$2 $3 $1 seed=$4 rc=$? $(( $(date +%s) - t0 )) s $(tail -n 1 $out/$2.$3.$1.txt | cut -c1-${WIDTH:-420})"
}
for arg in "$@"; do
  cell=${arg%%=*}; pairs=${arg##*=}
  i=0
  while [ $i -lt $pairs ]; do
    i=$((i + 1)); seed=$((seed + 1))
    if [ $((i % 2)) = 1 ]; then order="parent change"; else order="change parent"; fi
    for tree in $order; do run $tree $cell pair$i $seed 0; done
    if [ $i = 1 ] && [ "${TRACED:-}" = "$cell" ]; then
      seed=$((seed + 1))
      WIDTH=6000
      run change $cell traced $seed 1
      run parent $cell traced $seed 1
      WIDTH=420
    fi
  done
done
