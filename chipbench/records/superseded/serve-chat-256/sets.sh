# PR 46: the re-sized cell on ONE machine: two disjoint sets of untraced runs
# (tools/repeat.py, a process and a seed a run, 51 s), then one traced run with
# its wall time by the machine's own clock. Called once a machine; the seeds of
# the second machine's sets are the first's, so that every set has a set of the
# same seeds beside it (sets A and C, B and D).
#   chiprun --chips 1 --timeout 3500 -- sh chipbench/records/superseded/serve-chat-256/sets.sh <machine tag> <set> <seed0> <set> <seed0> <runs a set> <traced seed>
out=chiprun_out/serve-chat-loaded/$1; mkdir -p $out
cell=gpt2-125m.serve-chat
python3 -m chipbench.tools.repeat --workload $cell --runs $6 --seconds 51 --seed0 $3 --out $out/$2 2>&1 | cut -c1-900
python3 -m chipbench.tools.repeat --workload $cell --runs $6 --seconds 51 --seed0 $5 --out $out/$4 2>&1 | cut -c1-900
t0=$(python3 -c 'import time; print(time.time())')
python3 -m chipbench.run --workload $cell --seed $7 --seconds 51 --trace 1 > $out/traced.log 2> $out/traced.err
echo "traced rc=$? wall $(python3 -c "import time; print(round(time.time() - $t0, 1))") s" | tee $out/traced.wall; tail -c 6000 $out/traced.log; tail -c 1500 $out/traced.err
