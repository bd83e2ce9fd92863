# PR 46, after review: a third traced run of the cell (a third seed), from `git archive $(git write-tree)` unpacked.
#   chiprun --chips 1 --timeout 600 -- sh chipbench/records/serve-chat-loaded/traced3.sh <tag> <seed>
out=$PWD/chiprun_out/serve-chat-loaded/$1; mkdir -p $out
cd .archive_check || exit 2
[ -e .git ] && exit 2
t0=$(python3 -c 'import time; print(time.time())')
python3 -m chipbench.run --workload gpt2-125m.serve-chat --seed $2 --seconds 51 --trace 1 > $out/traced3.log 2> $out/traced3.err
echo "traced rc=$? wall $(python3 -c "import time; print(round(time.time() - $t0, 1))") s" | tee $out/traced3.wall; tail -c 4000 $out/traced3.log; tail -c 800 $out/traced3.err
