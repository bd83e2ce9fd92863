# PR 46, after review: the cell at the size its traffic occupies (64 slots, 28/s), from the COMMITTED files alone:
# `git archive $(git write-tree)` unpacked into .archive_check (listed in .gitignore), no git repository.
# Two sets of six untraced runs with the SAME seeds (tools/repeat.py, a process and a seed a run, 51 s), one traced
# run with its wall time by the machine's own clock, then the knee at 64 slots (tools/sweep.py, two seeds, 30 s
# passes, the first rate given twice because a process's first pass reads high and is discarded).
#   git add -A; rm -rf .archive_check; mkdir .archive_check; git archive $(git write-tree) | tar -x -C .archive_check
#   chiprun --chips 1 --timeout 3300 -- sh chipbench/records/serve-chat-loaded/cell.sh <tag> <seed0> <traced seed> "<rates>"
out=$PWD/chiprun_out/serve-chat-loaded/$1; mkdir -p $out
cell=gpt2-125m.serve-chat
cd .archive_check || exit 2
[ -e .git ] && exit 2
for s in setI setJ; do
  python3 -m chipbench.tools.repeat --workload $cell --runs 6 --seconds 51 --seed0 $2 --out $out/$s 2>&1 | cut -c1-700
done
t0=$(python3 -c 'import time; print(time.time())')
python3 -m chipbench.run --workload $cell --seed $3 --seconds 51 --trace 1 > $out/traced.log 2> $out/traced.err
echo "traced rc=$? wall $(python3 -c "import time; print(round(time.time() - $t0, 1))") s" | tee $out/traced.wall; tail -c 5000 $out/traced.log; tail -c 1200 $out/traced.err
[ -n "$4" ] || exit 0
for seed in 7 11; do
  t0=$(date +%s)
  python3 -m chipbench.tools.sweep --workload $cell --rates "$4" --seconds 30 --seed $seed > $out/sweep64_seed$seed.log 2> $out/sweep64_seed$seed.err
  echo "sweep seed $seed rc=$? in $(( $(date +%s) - t0 )) s"; cut -c1-600 $out/sweep64_seed$seed.log; tail -c 800 $out/sweep64_seed$seed.err
done
