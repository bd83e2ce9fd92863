# PR 51: what a prefill costs by the real tokens in its bucket, parent
# (.parent/: `git archive` of the parent commit with this PR's BENCHMARK.json
# and chipbench/ laid over it) and change (the tree itself, or CHANGE=<dir>),
# each cell's trace replayed through engine.prefill alone on ONE seed a cell,
# then made-up prompts of chosen lengths in the cell's longest bucket
# (replay_by_length.py). The first tokens of the two sides must be equal.
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/records/prefill-real-chunks/micro.sh <seed>
seed=$1
out=$PWD/chiprun_out/prefill-real-chunks; mkdir -p $out
here=chipbench/records/prefill-real-chunks
replay() {  # cell, lengths
  for tree in parent change; do
    if [ $tree = parent ]; then dir=.parent; else dir=${CHANGE:-.}; fi
    t0=$(date +%s)
    (cd $dir && python3 $here/replay_by_length.py --workload $1 --seed $seed --lengths $2 \
       > $out/replay.$1.$tree.txt 2> $out/replay.$1.$tree.err)
    echo "$1 $tree rc=$? in $(( $(date +%s) - t0 )) s"
    grep -E '"event"' $out/replay.$1.$tree.txt | cut -c1-300
  done
  python3 $here/pair_up.py $out/replay.$1.parent.txt $out/replay.$1.change.txt | tee $out/paired.$1.txt
}
replay k-exaone-236b-a23b.serve-mixed-len 32767,28672,24577,22646,20481,16385
replay mimo-v2.5.serve-code-agent 24575,22528,19982,18433,16385
