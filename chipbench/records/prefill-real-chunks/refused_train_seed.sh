# PR 51, after the driver's refusal (BENCHMARK_REFUSED.md): the PARENT's
# program on gpt2-125m.train-1chip, traced, on the seed the check named, once
# under this PR's benchmark files (.bench_check = bed5d80 with BENCHMARK.json
# and chipbench/ of this PR laid over) and once under the parent's own (.parent).
#   chiprun --chips 1 --timeout 1500 -- sh chipbench/records/prefill-real-chunks/refused_train_seed.sh 1995943484
out=$PWD/chiprun_out/prefill-real-chunks/refused_train_seed; mkdir -p $out
for tree in .bench_check .parent; do
  (cd $tree && python3 -m chipbench.run --workload gpt2-125m.train-1chip --seed $1 --seconds 51 --trace 1 > $out/traced$tree.txt 2> $out/traced$tree.err)
  echo "$tree rc=$?"
  grep -a '"event": "check"\|"event": "incorrect"' $out/traced$tree.txt | cut -c1-1500
  tail -n 1 $out/traced$tree.txt | cut -c1-400
done
