# PR 40, the last call: six other seeds under the rule as committed, the
# limits' readings again, and the cell traced and untraced from the files git
# would commit (.archive_check holds `git archive $(git write-tree)`, made
# before the call).
#   chiprun --chips 1 --timeout 3500 -- sh chipbench/records/k-exaone/final.sh
out=$PWD/chiprun_out/k-exaone; mkdir -p $out/archive
cell=k-exaone-236b-a23b.serve-mixed-len
python3 -m chipbench.tools.repeat --workload $cell --runs 6 --seconds 51 --seed0 2147483000 --out $out/setB 2>&1 | cut -c1-700
python3 -m chipbench.tools.check_limits_knobs --workload $cell --seed 3000000007 --seconds 20 > $out/limits_readings_final.log 2> $out/limits_readings_final.err
echo limits rc=$?; grep passes_the_rule $out/limits_readings_final.log | cut -c1-300
( cd .archive_check
  for trace in 0 1; do
    t0=$(date +%s)
    python3 -m chipbench.run --workload $cell --seed $((2147400000 + trace)) --seconds 51 --trace $trace > $out/archive/run.$trace.log 2> $out/archive/run.$trace.err
    echo "archive, trace $trace: rc=$? in $(( $(date +%s) - t0 )) s"; tail -n 1 $out/archive/run.$trace.log | cut -c1-1500; grep -c unread $out/archive/run.$trace.log
  done )
