# PR 23 after its review: the four-chip cell once with the reviewed harness
# (one run: what the chip budget left; its two sets of four under
# records/cell4 are of the same step program)
set -x
OUT=$PWD/chiprun_out/review4
mkdir -p $OUT
ls -la --time-style=full-iso "$JAX_COMPILATION_CACHE_DIR" > $OUT/cache_before.txt 2>&1
python3 -m chipbench.run --workload gpt2-large-774m.train-fsdp-4chip --seed 2147480000 --seconds 30 --trace 0 > $OUT/gpt2-large-774m.train-fsdp-4chip.0.log 2> $OUT/run0.err
echo rc=$?
tail -c 4000 $OUT/gpt2-large-774m.train-fsdp-4chip.0.log; tail -c 1500 $OUT/run0.err
true
