# PR 41, the final tree: one untraced and one traced run of the cell from the
# files git would commit (.archive_check holds `git archive $(git write-tree)`,
# made before the call), each on a seed no other run of this PR used.
#   chiprun --chips 1 --timeout 1200 -- sh chipbench/records/k-exaone-compact/archive.sh
out=$PWD/chiprun_out/pr41/archive; mkdir -p $out; cell=k-exaone-236b-a23b.serve-mixed-len
cd .archive_check
python3 -m chipbench.run --workload $cell --seed 2147412041 --seconds 51 --trace 0 > $out/untraced.log 2> $out/untraced.err
echo "untraced: rc=$?"; tail -n 1 $out/untraced.log | cut -c1-600
t0=$(date +%s)
python3 -m chipbench.run --workload $cell --seed 2147413041 --seconds 51 --trace 1 > $out/traced.log 2> $out/traced.err
echo "traced: rc=$? in $(( $(date +%s) - t0 )) s; unread lines: $(grep -c unread $out/traced.log)"; tail -n 1 $out/traced.log | cut -c1-2500
