# the four-chip cell: a traced run first (it compiles everything and shows
# whether the batch fits), then two sets with the same seeds
set -x
CELL=gpt2-large-774m.train-fsdp-4chip
RUNS=${1:-4}
mkdir -p chiprun_out/cell4
python3 -m chipbench.run --workload $CELL --seed 2147483999 --seconds 51 --trace 1 > chiprun_out/cell4/traced.log 2> chiprun_out/cell4/traced.err
tail -c 2500 chiprun_out/cell4/traced.log; tail -c 2500 chiprun_out/cell4/traced.err
if ! tail -n 1 chiprun_out/cell4/traced.log | grep -q '"correct": true'; then
  echo "FALLBACK: global batch 8"
  sed -i 's/"batch": 16/"batch": 8/; s/"chunk_steps": 4/"chunk_steps": 8/' chipbench/traffic/lm-train-fsdp-4chip.json
  python3 -m chipbench.run --workload $CELL --seed 2147483999 --seconds 51 --trace 1 > chiprun_out/cell4/traced_b8.log 2> chiprun_out/cell4/traced_b8.err
  tail -c 2500 chiprun_out/cell4/traced_b8.log; tail -c 2500 chiprun_out/cell4/traced_b8.err
  tail -n 1 chiprun_out/cell4/traced_b8.log | grep -q '"correct": true' || exit 7
fi
cp chipbench/traffic/lm-train-fsdp-4chip.json chiprun_out/cell4/traffic_as_run.json
python3 -m chipbench.tools.repeat --workload $CELL --runs $RUNS --seconds 51 --seed0 2147480000 --out chiprun_out/cell4/setA
python3 -m chipbench.tools.repeat --workload $CELL --runs $RUNS --seconds 51 --seed0 2147480000 --out chiprun_out/cell4/setB
true
