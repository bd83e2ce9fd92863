# PR 45 after review, the final tree, from the files git would commit
# (.archive_check holds `git archive $(git write-tree)`, .bench_check the parent
# commit 3fc1df9 under this PR's BENCHMARK.json and chipbench/; both made before
# the call): the cell traced, two more seeds, and the parent on the new cell
# (must exit non-zero at once), on the machine's own compile cache.
#   chiprun --chips 1 --timeout 1800 -- sh chipbench/records/kimi-linear/final_review.sh
out=$PWD/chiprun_out/kimi-linear/final_review; mkdir -p $out
cell=kimi-linear-48b-a3b.serve-long-answer
cd .archive_check
t0=$(date +%s)
python3 -m chipbench.run --workload $cell --seed 2151400157 --seconds 51 --trace 1 > $out/traced.log 2> $out/traced.err
echo "traced rc=$? in $(( $(date +%s) - t0 )) s; unread lines: $(grep -c unread $out/traced.log)"; grep -h '"event": "check"' $out/traced.log | cut -c1-600; tail -n 1 $out/traced.log | cut -c1-6000; tail -c 600 $out/traced.err
python3 -m chipbench.tools.repeat --workload $cell --runs 2 --seconds 51 --seed0 2152486061 --out $out/seeds 2>&1 | cut -c1-420
for f in $out/seeds/$cell.*.log; do grep -h '"event": "setup"' $f | cut -c1-150; grep -h '"event": "check"\|"event": "sweep"' $f | cut -c1-900; done
cd ../.bench_check
t0=$(date +%s)
timeout 600 python3 -m chipbench.run --workload $cell --seed 2153483057 --seconds 51 --trace 0 > $out/parent_newcell.out 2> $out/parent_newcell.err
echo "parent, new cell: rc=$? in $(( $(date +%s) - t0 )) s"; tail -n 4 $out/parent_newcell.err | cut -c1-300
