# PR 51: cell 7's traced pair (the changed loop, which skips nothing there:
# prefill_computed_tokens_pct must read 100), change then parent.
#   chiprun --chips 1 --timeout 1800 -- sh chipbench/records/prefill-real-chunks/cell7_traced.sh <seed>
out=$PWD/chiprun_out/prefill-real-chunks/cell7; mkdir -p $out
for tree in .archive_check .parent; do
  (cd $tree && python3 -m chipbench.run --workload kimi-linear-48b-a3b.serve-long-answer --seed $1 --seconds 51 --trace 1 > $out/traced$tree.txt 2> $out/traced$tree.err)
  echo "$tree rc=$?"; tail -n 1 $out/traced$tree.txt | cut -c1-3500
done
