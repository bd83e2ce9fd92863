# PR 23 after its review: the two one-chip train cells with the rate taken
# over the whole window, no gate, and the reference trained beside the
# program. Cell 1 runs from a checkout made of the committed files alone
# (.bench_check/, unpacked from `git archive $(git write-tree)` before the
# call), so its first run compiles and the others find the cache in place.
set -x
OUT=$PWD/chiprun_out/review
mkdir -p $OUT
env | grep -i -E "jax|xla" > $OUT/env.txt
ls -la --time-style=full-iso "$JAX_COMPILATION_CACHE_DIR" > $OUT/cache_before.txt 2>&1
du -sh "$JAX_COMPILATION_CACHE_DIR" >> $OUT/cache_before.txt 2>&1
( cd .bench_check && ls && env -u JAX_COMPILATION_CACHE_DIR python3 -m chipbench.tools.repeat \
    --workload gpt2-125m.train-1chip --runs 3 --seconds 51 --seed0 2147480000 --out $OUT/archive \
  && env -u JAX_COMPILATION_CACHE_DIR python3 -m chipbench.run --workload gpt2-125m.train-1chip \
    --seed 2147483999 --seconds 51 --trace 1 > $OUT/archive/traced.log 2> $OUT/archive/traced.err; \
  tail -c 1500 $OUT/archive/traced.log; du -sh .jax_cache )
python3 -m chipbench.tools.repeat --workload resnet50.train-1chip --runs 2 --seconds 51 --seed0 2147480000 --out $OUT/one
mkdir -p $OUT/bare/chipbench && cp -r BENCHMARK.json $OUT/bare/ && cp -r chipbench/*.py chipbench/drivers chipbench/families chipbench/readers chipbench/references chipbench/configs chipbench/traffic chipbench/metrics chipbench/tools $OUT/bare/chipbench/
( cd $OUT/bare && python3 -m chipbench.run --workload gpt2-125m.train-1chip --seed 1 --seconds 5 --trace 0 > bare.out 2> bare.err; echo "bare rc=$? stdout bytes=$(wc -c < bare.out)"; tail -n 2 bare.err )
rm -rf $OUT/bare/chipbench
true
