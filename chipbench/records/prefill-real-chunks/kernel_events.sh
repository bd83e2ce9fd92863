# PR 51: one traced run of cell 6 in each tree on one seed, and what each
# trace holds of the full layer's kernel (kernel_events.py).
#   chiprun --chips 1 --timeout 1500 -- sh chipbench/records/prefill-real-chunks/kernel_events.sh <seed>
# (ORDER='.parent .archive_check' through `env` traces the parent first.)
out=$PWD/chiprun_out/prefill-real-chunks/kernel_events; mkdir -p $out
here=chipbench/records/prefill-real-chunks
for tree in ${ORDER:-.archive_check .parent}; do
  (cd $tree && python3 -m chipbench.run --workload k-exaone-236b-a23b.serve-mixed-len --seed $1 --seconds 51 --trace 1 > $out/traced$tree.txt 2> $out/traced$tree.err)
  echo "$tree rc=$?"; tail -n 1 $out/traced$tree.txt | cut -c1-3000
  (cd $tree && python3 $here/kernel_events.py . > $out/events$tree.txt 2> $out/events$tree.err); echo "events rc=$?"
  tail -n 3 $out/events$tree.err
done
