#!/bin/sh
# PR 48: parent (.parent/, `git archive` of 16c3d1a) and change alternating
# on one machine, each tree's first run (its cold compile: `first_setup_s`)
# apart from the warm pairs; the two sides of a pair share a seed, no two
# pairs do. Every run's whole output is kept: the `setup` event has
# `programs`, `compile_s`, `cache_hits`, the last line `setup_s` and
# `serve_ttft_p95_ms`.
#   chiprun --chips 1 --timeout 3500 -- sh chipbench/records/grouped-matmul/pairs.sh <cell> <first seed> <pairs> [trace]
cell=$1; seed=$2; pairs=$3; traced=${4:-}
out=$PWD/chiprun_out/grouped-matmul/$cell; mkdir -p $out
run() {  # tree, label, seed, trace
  if [ $1 = parent ]; then dir=.parent; else dir=.; fi
  (cd $dir && python3 -m chipbench.run --workload $cell --seed $3 --seconds 51 --trace $4 \
     > $out/$2_$1.log 2>$out/$2_$1.err)
  echo "$2 $1 seed=$3 rc=$? $(tail -n 1 $out/$2_$1.log | cut -c1-330)"
}
# a cold compile each: the machine keeps ONE cache directory for both trees
# (JAX_COMPILATION_CACHE_DIR; their programs have other keys, a Mosaic
# kernel's body holds its source path), emptied before each tree's first run
# and put together again after; KEEP=1 leaves the cache as it came
cache=${JAX_COMPILATION_CACHE_DIR:-}
if [ -z "${KEEP:-}" ] && [ -n "$cache" ]; then rm -rf "$cache"; mkdir -p "$cache"; fi
run parent first $seed 0
if [ -z "${KEEP:-}" ] && [ -n "$cache" ]; then mv "$cache" "$cache.parent"; mkdir -p "$cache"; fi
run change first $seed 0
if [ -z "${KEEP:-}" ] && [ -n "$cache" ]; then cp -n "$cache.parent"/* "$cache"/; rm -rf "$cache.parent"; fi
i=0
while [ $i -lt $pairs ]; do
  i=$((i + 1)); seed=$((seed + 1))
  if [ $((i % 2)) = 1 ]; then order="parent change"; else order="change parent"; fi
  for tree in $order; do run $tree pair$i $seed 0; done
done
if [ -n "$traced" ]; then
  seed=$((seed + 1))
  run parent traced $seed 1
  run change traced $seed 1
fi
