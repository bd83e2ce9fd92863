# PR 23 after its review, second call: the checked updates all train on the
# run's first batch; how far the program's loss lies from the reference's
set -x
OUT=$PWD/chiprun_out/review2
for cell in gpt2-125m.train-1chip resnet50.train-1chip; do
  python3 -m chipbench.tools.repeat --workload $cell --runs 1 --seconds 51 --seed0 2147480000 --out $OUT/at51
  python3 -m chipbench.tools.repeat --workload $cell --runs 1 --seconds 20 --seed0 2148480003 --out $OUT/at20
  grep -h '"event": "check"' $OUT/at51/$cell.0.log $OUT/at20/$cell.0.log | cut -c1-700
done
true
