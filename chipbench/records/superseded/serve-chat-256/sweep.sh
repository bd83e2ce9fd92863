# PR 46: the knee of gpt2-125m.serve-chat at 256 slots, found again on this
# tree (no program file differs from the parent's). Two seeds, a process each;
# 30 s passes; the first rate is given twice, because a process's first pass
# reads high (PERF.md section 7, From PR 33 (3)) and is discarded. Then the
# cell once untraced and once traced at the rate its file holds (provisional:
# 100/s), for the set-up, the memory and the traced run's wall time.
#   chiprun --chips 1 --timeout 3300 -- sh chipbench/records/superseded/serve-chat-256/sweep.sh "70,70,85,100,110,120,130,140,150,165" [first]
out=chiprun_out/serve-chat-loaded; mkdir -p $out
cell=gpt2-125m.serve-chat
for seed in 7 11; do
  t0=$(date +%s)
  python3 -m chipbench.tools.sweep --workload $cell --rates "$1" --seconds 30 --seed $seed > $out/sweep_seed$seed.log 2> $out/sweep_seed$seed.err
  echo "sweep seed $seed rc=$? in $(( $(date +%s) - t0 )) s"; cut -c1-700 $out/sweep_seed$seed.log; tail -c 1200 $out/sweep_seed$seed.err
done
[ "$2" = first ] || exit 0
t0=$(python3 -c 'import time; print(time.time())')
python3 -m chipbench.run --workload $cell --seed 2147483946 --seconds 51 --trace 0 > $out/first_run.log 2> $out/first_run.err
echo "first run rc=$? in $(python3 -c "import time; print(round(time.time() - $t0, 1))") s"; tail -c 4000 $out/first_run.log; tail -c 1500 $out/first_run.err
t0=$(python3 -c 'import time; print(time.time())')
python3 -m chipbench.run --workload $cell --seed 2147483947 --seconds 51 --trace 1 > $out/first_traced.log 2> $out/first_traced.err
echo "first traced rc=$? in $(python3 -c "import time; print(round(time.time() - $t0, 1))") s"; tail -c 6000 $out/first_traced.log; tail -c 1500 $out/first_traced.err
