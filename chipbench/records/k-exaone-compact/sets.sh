# PR 41: six seeds of the cell untraced on the change (tools/repeat.py).
#   chiprun --chips 1 --timeout 2400 -- sh chipbench/records/k-exaone-compact/sets.sh <set> <seed0>
out=$PWD/chiprun_out/pr41; cell=k-exaone-236b-a23b.serve-mixed-len
python3 -m chipbench.tools.repeat --workload $cell --runs 6 --seconds 51 --seed0 $2 --out $out/$1 2>&1 | cut -c1-330
