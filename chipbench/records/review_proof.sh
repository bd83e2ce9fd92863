# PR 23, the final tree from its committed files alone: .bench_check/ is
# `git archive $(git write-tree)` unpacked before the call; cell 1 once, a
# short window, compile cache inside that checkout (so it compiles).
set -x
OUT=$PWD/chiprun_out/review_proof
mkdir -p $OUT
cd .bench_check && env -u JAX_COMPILATION_CACHE_DIR python3 -m chipbench.run --workload gpt2-125m.train-1chip \
  --seed 2147483999 --seconds 10 --trace 0 > $OUT/gpt2-125m.train-1chip.log 2> $OUT/err.txt
echo rc=$?
tail -c 2500 $OUT/gpt2-125m.train-1chip.log; tail -c 600 $OUT/err.txt; ls -d .jax_cache && du -sh .jax_cache
