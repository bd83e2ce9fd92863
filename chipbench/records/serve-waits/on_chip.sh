# ISSUE 42's runs of one cell on the chip: parent commit and change in turn
# on ONE machine, from ONE path, one compile cache (runs.sh).
#   sh chipbench/records/serve-waits/prepare.sh <parent commit>      (here)
#   chiprun --chips 1 --timeout 3500 -- sh chipbench/records/serve-waits/on_chip.sh <tag> <base seed> <pairs> <cell>
# Parent untraced (the first run from this path: cold where a program's key
# holds the path), parent traced, change traced (RIGHT AFTER the parent: its
# `setup` line must read as a second run of the parent's: `programs`,
# `compile_s`, `cache_hits`), change untraced on the first seed; then
# <pairs> - 1 more pairs, the two runs of a pair on one seed. Everything a
# run printed is under chiprun_out/pr42/<tag>/.
out=$PWD/chiprun_out/pr42/$1; base=$2; cell=$4; inside=--inside
. "$(dirname "$0")/runs.sh"
run parent $cell $((base + 1)) 0 parent.1
run parent $cell $((base + 500)) 1 parent.traced
run change $cell $((base + 500)) 1 change.traced
run change $cell $((base + 1)) 0 change.1
pairs $cell $base 2 $3
done_runs
