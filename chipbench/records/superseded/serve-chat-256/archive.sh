# PR 46: the committed files are enough: the cell untraced and traced from `git archive $(git write-tree)` unpacked
# into .archive_check (listed in .gitignore), which is no git repository and holds nothing that git would not commit.
#   git add -A; rm -rf .archive_check; mkdir .archive_check; git archive $(git write-tree) | tar -x -C .archive_check
#   chiprun --chips 1 --timeout 1500 -- sh chipbench/records/superseded/serve-chat-256/archive.sh
mkdir -p chiprun_out/serve-chat-loaded
cd .archive_check
for t in 0 1; do
  t0=$(python3 -c 'import time; print(time.time())')
  python3 -m chipbench.run --workload gpt2-125m.serve-chat --seed $((3000000046 + t)) --seconds 51 --trace $t > ../chiprun_out/serve-chat-loaded/archive_trace$t.log 2> ../chiprun_out/serve-chat-loaded/archive_trace$t.err
  echo "archive run trace $t rc=$? wall $(python3 -c "import time; print(round(time.time() - $t0, 1))") s"; tail -c 2500 ../chiprun_out/serve-chat-loaded/archive_trace$t.log; tail -c 600 ../chiprun_out/serve-chat-loaded/archive_trace$t.err
done
