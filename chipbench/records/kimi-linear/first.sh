# PR 45, the new cell's first time on the chip: ops/kda.py's two forms at the
# cell's shapes, the cell once untraced at a provisional rate, then the sweep
# on the first seed (a process's first pass is discarded: the rate twice).
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/records/kimi-linear/first.sh
out=chiprun_out/kimi-linear; mkdir -p $out
cell=kimi-linear-48b-a3b.serve-long-answer
python3 chip_kernel_parity.py kda > $out/kda_parity.log 2> $out/kda_parity.err
echo parity rc=$?; cat $out/kda_parity.log | cut -c1-600; tail -c 1500 $out/kda_parity.err
t0=$(date +%s)
python3 -m chipbench.run --workload $cell --seed 2147483945 --seconds 51 --trace 0 > $out/first_run.log 2> $out/first_run.err
echo "first run rc=$? in $(( $(date +%s) - t0 )) s"; tail -c 5000 $out/first_run.log; tail -c 2500 $out/first_run.err
t0=$(date +%s)
python3 -m chipbench.tools.sweep --workload $cell --rates ${RATES:-3,3,4,5,6,7,8} --seconds 30 --seed 7 > $out/sweep_seed7.log 2> $out/sweep_seed7.err
echo "sweep rc=$? in $(( $(date +%s) - t0 )) s"; cut -c1-700 $out/sweep_seed7.log; tail -c 1500 $out/sweep_seed7.err
