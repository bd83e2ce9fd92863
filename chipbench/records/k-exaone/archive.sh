# PR 40, the final tree: six new seeds untraced and one traced run of the cell
# from the files git would commit (.archive_check holds
# `git archive $(git write-tree)`, made before the call).
#   chiprun --chips 1 --timeout 2400 -- sh chipbench/records/k-exaone/archive.sh
out=$PWD/chiprun_out/k-exaone; cell=k-exaone-236b-a23b.serve-mixed-len
cd .archive_check
python3 -m chipbench.tools.repeat --workload $cell --runs 6 --seconds 51 --seed0 2147487000 --out $out/setC 2>&1 | cut -c1-330
t0=$(date +%s)
python3 -m chipbench.run --workload $cell --seed 2147400002 --seconds 51 --trace 1 > $out/setC/traced.log 2> $out/setC/traced.err
echo "traced: rc=$? in $(( $(date +%s) - t0 )) s; unread lines: $(grep -c unread $out/setC/traced.log)"; tail -n 1 $out/setC/traced.log | cut -c1-2500
