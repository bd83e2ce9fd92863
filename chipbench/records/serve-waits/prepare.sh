# Before a call of on_chip.sh or archive.sh, HERE (the chip machine has no
# git): two trees under .bench_check (listed in .gitignore, copied to the
# chip with the rest):
#   parent/  `git archive <parent commit>` with this PR's BENCHMARK.json and
#            chipbench/ laid over it, as the driver's check lays them
#   change/  the files git would commit from this tree; with `index`, after
#            `git add -A`, exactly `git archive $(git write-tree)`
#   sh chipbench/records/serve-waits/prepare.sh <parent commit> [index]
set -e
rm -rf .bench_check; mkdir -p .bench_check/parent .bench_check/change
git archive "$1" | tar -x -C .bench_check/parent
if [ "$2" = index ]; then git archive "$(git write-tree)" | tar -x -C .bench_check/change
else git ls-files -co --exclude-standard | tar -c -T - | tar -x -C .bench_check/change; fi
cp -r .bench_check/change/BENCHMARK.json .bench_check/change/chipbench .bench_check/parent/
du -sh .bench_check/parent .bench_check/change
