# PR 46, after review: the cell from the files as they are committed at the end (the traffic file's note and knee and
# BENCHMARK.json's why and bound written after cell.sh's call), on another machine: `git archive $(git write-tree)`
# unpacked into .archive_check, six untraced runs on seeds no set has had (set K) and one traced run.
#   git add -A; rm -rf .archive_check; mkdir .archive_check; git archive $(git write-tree) | tar -x -C .archive_check
#   chiprun --chips 1 --timeout 1500 -- sh chipbench/records/serve-chat-loaded/final.sh <tag> <seed0> <traced seed>
out=$PWD/chiprun_out/serve-chat-loaded/$1; mkdir -p $out
cell=gpt2-125m.serve-chat
cd .archive_check || exit 2
[ -e .git ] && exit 2
python3 -m chipbench.tools.repeat --workload $cell --runs 6 --seconds 51 --seed0 $2 --out $out/setK 2>&1 | cut -c1-700
t0=$(python3 -c 'import time; print(time.time())')
python3 -m chipbench.run --workload $cell --seed $3 --seconds 51 --trace 1 > $out/traced.log 2> $out/traced.err
echo "traced rc=$? wall $(python3 -c "import time; print(round(time.time() - $t0, 1))") s" | tee $out/traced.wall; tail -c 5000 $out/traced.log; tail -c 1200 $out/traced.err
