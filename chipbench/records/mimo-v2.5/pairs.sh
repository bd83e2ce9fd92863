# PR 49: the old cells that run code this PR changed or sits beside, parent
# (.parent/: `git archive` of 1f65d14 with this PR's BENCHMARK.json and
# chipbench/ laid over it, as the driver's check does) and change
# (.archive_check/: `git archive $(git write-tree)`) in turn on one machine,
# the two sides of a pair on one seed, no two pairs on one; who goes first
# alternates. First: the parent under the new files must fail AT ONCE on the
# new cell.
#   chiprun --chips 1 --timeout 3550 -- env TRACED=<cell> sh chipbench/records/mimo-v2.5/pairs.sh <first seed> <pairs> <cell>[=<pairs>] [cell ...]
# (TRACED: that cell gets a traced pair too; ONLY_PAIRS=1 skips the first and
# the last step. The environment goes through `env`: the chip tool does not
# forward its caller's.) Last, from the committed files,
# chip_kernel_parity.py gqa_uneven again.
seed=$1; pairs=$2; shift 2
out=$PWD/chiprun_out/mimo-v2.5/pairs; mkdir -p $out
if [ -z "${ONLY_PAIRS:-}" ]; then
t0=$(date +%s)
(cd .parent && timeout 600 python3 -m chipbench.run --workload mimo-v2.5.serve-code-agent --seed 2147483046 --seconds 51 --trace 0 > $out/parent_newcell.out 2> $out/parent_newcell.err)
echo $? > $out/parent_newcell.rc; echo "parent, new cell: rc=$(cat $out/parent_newcell.rc) in $(( $(date +%s) - t0 )) s"; tail -n 3 $out/parent_newcell.err | cut -c1-300
fi
run() {  # tree, cell, label, seed, trace
  if [ $1 = parent ]; then dir=.parent; else dir=.archive_check; fi
  (cd $dir && python3 -m chipbench.run --workload $2 --seed $4 --seconds 51 --trace $5 \
     > $out/$2.$3.$1.log 2>$out/$2.$3.$1.err)
  echo "$2 $3 $1 seed=$4 rc=$? $(grep -c unread $out/$2.$3.$1.log) unread $(tail -n 1 $out/$2.$3.$1.log | cut -c1-${WIDTH:-400})"
}
for arg in "$@"; do
  cell=${arg%%=*}                       # <cell> or <cell>=<pairs>
  case $arg in *=*) pairs=${arg##*=};; esac
  i=0
  while [ $i -lt $pairs ]; do
    i=$((i + 1)); seed=$((seed + 1))
    if [ $((i % 2)) = 1 ]; then order="parent change"; else order="change parent"; fi
    for tree in $order; do run $tree $cell pair$i $seed 0; done
  done
  if [ "${TRACED:-}" = "$cell" ]; then
    seed=$((seed + 1))
    WIDTH=3000
    run parent $cell traced $seed 1
    run change $cell traced $seed 1
    WIDTH=400
  fi
done
[ -n "${ONLY_PAIRS:-}" ] && exit 0
(cd .archive_check && python3 chip_kernel_parity.py gqa_uneven > $out/../kernel_parity_gqa_uneven_final.log 2> $out/../kernel_parity_gqa_uneven_final.err)
echo "parity rc=$?"; tail -n 1 $out/../kernel_parity_gqa_uneven_final.log
