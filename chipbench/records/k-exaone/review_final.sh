# PR 40 after its review, the FINAL tree: one new seed untraced and one traced run of the cell from the files git would
# commit (.archive_check holds `git archive $(git write-tree)`, made before the call), and the compile cache's entry count.
#   chiprun --chips 1 --timeout 900 -- sh chipbench/records/k-exaone/review_final.sh
out=$PWD/chiprun_out/k-exaone/review_final; cell=k-exaone-236b-a23b.serve-mixed-len; mkdir -p $out; cd .archive_check
t0=$(date +%s); python3 -m chipbench.run --workload $cell --seed 2147489003 --seconds 51 --trace 0 > $out/run.log 2> $out/run.err
echo "untraced: rc=$? in $(( $(date +%s) - t0 )) s"; grep '"event": "check"' $out/run.log | cut -c1-420; tail -n 1 $out/run.log | cut -c1-330
t0=$(date +%s); python3 -m chipbench.run --workload $cell --seed 2147400004 --seconds 51 --trace 1 > $out/traced.log 2> $out/traced.err
echo "traced: rc=$? in $(( $(date +%s) - t0 )) s; unread lines: $(grep -c unread $out/traced.log)"; tail -n 1 $out/traced.log | cut -c1-1600
ls "${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}" | grep -c -- "-cache$"
