# one set of six runs of every one-chip cell at run_seconds, the same seeds in
# every set; with TRACED=1 also one traced run of each
set -x
SET=$1; TRACED=${2:-0}
for cell in gpt2-125m.serve-chat gpt2-125m.train-1chip resnet50.train-1chip; do
  python3 -m chipbench.tools.repeat --workload $cell --runs 6 --seconds 51 --seed0 2147480000 --out chiprun_out/$SET
  if [ "$TRACED" = 1 ]; then
    mkdir -p chiprun_out/traced
    python3 -m chipbench.run --workload $cell --seed 2147483999 --seconds 51 --trace 1 > chiprun_out/traced/$cell.log 2> chiprun_out/traced/$cell.err
    tail -c 1500 chiprun_out/traced/$cell.log
  fi
done
true
