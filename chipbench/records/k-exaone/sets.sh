# PR 40: the cell six seeds untraced (tools/repeat.py), one traced run, the
# readings its limits lie between, and the parent commit under this PR's
# benchmark files (the new cell must fail at once; an old cell traced must run).
#   chiprun --chips 1 --timeout 3500 -- sh chipbench/records/k-exaone/sets.sh <set name> <seed0> [traced] [limits] [parent]
out=chiprun_out/k-exaone; mkdir -p $out
cell=k-exaone-236b-a23b.serve-mixed-len
set_name=$1; seed0=$2; shift 2
python3 -m chipbench.tools.repeat --workload $cell --runs 6 --seconds 51 --seed0 $seed0 --out $out/$set_name 2>&1 | cut -c1-900
for what in "$@"; do
  case $what in
  traced)
    python3 -m chipbench.run --workload $cell --seed 2147483647 --seconds 51 --trace 1 > $out/traced.log 2> $out/traced.err
    echo traced rc=$?; tail -c 5000 $out/traced.log; tail -c 1500 $out/traced.err;;
  limits)
    python3 -m chipbench.tools.check_limits_knobs --workload $cell --seed 3000000007 --seconds 20 > $out/limits_readings.log 2> $out/limits_readings.err
    echo limits rc=$?; cut -c1-1200 $out/limits_readings.log | tail -20; tail -c 1500 $out/limits_readings.err;;
  parent)
    # .bench_check holds `git archive` of the parent commit (made before the call)
    cp BENCHMARK.json .bench_check/; cp -r chipbench/. .bench_check/chipbench/
    ( cd .bench_check
      t0=$(date +%s)
      python3 -m chipbench.run --workload $cell --seed 5 --seconds 51 --trace 0 > ../$out/parent_newcell.log 2> ../$out/parent_newcell.err
      echo "parent, new cell: rc=$? in $(( $(date +%s) - t0 )) s"; tail -c 600 ../$out/parent_newcell.err
      python3 -m chipbench.run --workload gpt2-125m.serve-chat --seed 2147480001 --seconds 51 --trace 1 > ../$out/parent_serve-chat.1.log 2> ../$out/parent_serve-chat.1.err
      echo "parent, serve-chat traced: rc=$?"; tail -c 2500 ../$out/parent_serve-chat.1.log );;
  esac
done
