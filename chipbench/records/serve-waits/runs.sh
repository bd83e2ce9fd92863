# Sourced by on_chip.sh, archive.sh and steady.sh: `run <side> <cell> <seed> <0 plain | 1 traced | 2 timed_steps.py> <name>`
# runs one cell from .bench_check/tree, the ONE path both sides are renamed
# into for their runs (a Mosaic kernel's serialized body carries its source
# files' full paths and the compile cache's key hashes it), under one compile
# cache, and prints a line: the `setup` line's compile counts, an `unread`
# line, the start of the result line. Everything else goes to $out.
mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}
echo "compile cache: $JAX_COMPILATION_CACHE_DIR ($(du -sm $JAX_COMPILATION_CACHE_DIR 2>/dev/null | cut -f1) MiB)"
now=none
run() {
  if [ $now != $1 ]; then
    [ $now != none ] && mv .bench_check/tree .bench_check/$now
    mv .bench_check/$1 .bench_check/tree; now=$1
  fi
  log=$out/$2.$5.log
  t0=$(date +%s)
  ( cd .bench_check/tree
    if [ $4 = 1 ]; then
      python3 chipbench/records/serve-waits/traced_run.py $out/$2.$5.json $inside --workload $2 --seed $3 --seconds 51
    elif [ $4 = 2 ]; then
      python3 chipbench/records/serve-waits/timed_steps.py $out/$2.$5.steps.json --workload $2 --seed $3 --seconds 51
    else
      python3 -m chipbench.run --workload $2 --seed $3 --seconds 51 --trace 0
    fi ) > $log 2> $out/$2.$5.err
  echo "$2 $5 seed $3 rc=$? wall=$(( $(date +%s) - t0 ))s"
  grep '"event": "setup"' $log | python3 -c 'import json,sys
for l in sys.stdin:
    r = json.loads(l); print("   setup:", {k: r[k] for k in ("programs", "compile_s", "cache_hits")})'
  grep '"event": "unread"' $log | cut -c1-400
  echo "   $(tail -n 1 $log | cut -c1-900)"
}
pairs() {  # <cell> <base seed> <first> <last> [2]: untraced pairs, alternating which side goes first
  i=$3
  while [ $i -le $4 ]; do
    if [ $((i % 2)) = 0 ]; then order="change parent"; else order="parent change"; fi
    for side in $order; do run $side $1 $(($2 + i)) ${5:-0} $side.$i; done
    i=$((i + 1))
  done
}
done_runs() {
  mv .bench_check/tree .bench_check/$now
  echo "compile cache after: $(du -sm $JAX_COMPILATION_CACHE_DIR 2>/dev/null | cut -f1) MiB"
}
