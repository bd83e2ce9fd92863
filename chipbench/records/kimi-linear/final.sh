# PR 45, the final tree, from the files git would commit (.archive_check holds
# `git archive $(git write-tree)`, .bench_check the parent commit 3fc1df9 under
# this PR's BENCHMARK.json and chipbench/; both made before the call):
# the limits' readings (every degraded reference must fail, the program pass),
# the cell traced, three more seeds; then the parent under the new benchmark
# (the new cell must exit non-zero at once; an old cell traced, no `unread`)
# and that old cell traced on the change, same seed.
#   chiprun --chips 1 --timeout 3550 -- sh chipbench/records/kimi-linear/final.sh
out=$PWD/chiprun_out/kimi-linear/final; mkdir -p $out/parent_under
cell=kimi-linear-48b-a3b.serve-long-answer
old=${OLD:-xing4.0-29b-a4b.serve-docqa}
cd .archive_check
t0=$(date +%s)
python3 -m chipbench.tools.check_limits_knobs --workload $cell --seed 2147483845 --seconds 20 > $out/limits_readings_final.log 2> $out/limits_readings_final.err
echo "limits rc=$? in $(( $(date +%s) - t0 )) s"; grep -h 'passes_the_rule\|"ok"' $out/limits_readings_final.log | cut -c1-300; tail -c 800 $out/limits_readings_final.err
t0=$(date +%s)
python3 -m chipbench.run --workload $cell --seed 2147400145 --seconds 51 --trace 1 > $out/traced.log 2> $out/traced.err
echo "traced rc=$? in $(( $(date +%s) - t0 )) s; unread lines: $(grep -c unread $out/traced.log)"; tail -n 1 $out/traced.log | cut -c1-5000; tail -c 800 $out/traced.err
python3 -m chipbench.tools.repeat --workload $cell --runs 3 --seconds 51 --seed0 2147486045 --out $out/setB 2>&1 | cut -c1-420
for f in $out/setB/$cell.*.log; do grep -h '"event": "check"\|"event": "sweep"' $f | cut -c1-900; done
t0=$(date +%s)
python3 -m chipbench.run --workload $old --seed 2147483047 --seconds 51 --trace 1 > $out/parent_under/$old.change.traced.log 2> $out/parent_under/$old.change.traced.err
echo "change, $old traced: rc=$? in $(( $(date +%s) - t0 )) s; unread lines: $(grep -c unread $out/parent_under/$old.change.traced.log)"; grep -h '"event": "setup"' $out/parent_under/$old.change.traced.log | cut -c1-200; tail -n 1 $out/parent_under/$old.change.traced.log | cut -c1-1800
cd ../.bench_check
t0=$(date +%s)
timeout 600 python3 -m chipbench.run --workload $cell --seed 2147483046 --seconds 51 --trace 0 > $out/parent_under/newcell.out 2> $out/parent_under/newcell.err
echo $? > $out/parent_under/newcell.rc; echo "parent, new cell: rc=$(cat $out/parent_under/newcell.rc) in $(( $(date +%s) - t0 )) s"; tail -n 4 $out/parent_under/newcell.err | cut -c1-300
t0=$(date +%s)
python3 -m chipbench.run --workload $old --seed 2147483047 --seconds 51 --trace 1 > $out/parent_under/$old.parent.traced.log 2> $out/parent_under/$old.parent.traced.err
echo "parent, $old traced: rc=$? in $(( $(date +%s) - t0 )) s; unread lines: $(grep -c unread $out/parent_under/$old.parent.traced.log)"; grep -h '"event": "setup"' $out/parent_under/$old.parent.traced.log | cut -c1-200; tail -n 1 $out/parent_under/$old.parent.traced.log | cut -c1-1800
for side in parent change; do
  if [ $side = parent ]; then cd ../.bench_check; else cd ../.archive_check; fi
  python3 -m chipbench.run --workload gpt2-125m.serve-chat --seed 2147483048 --seconds 51 --trace 0 > $out/parent_under/serve-chat.$side.log 2> $out/parent_under/serve-chat.$side.err
  echo "$side serve-chat rc=$?"; grep -h '"event": "setup"' $out/parent_under/serve-chat.$side.log | cut -c1-160; tail -n 1 $out/parent_under/serve-chat.$side.log | cut -c1-500
done
