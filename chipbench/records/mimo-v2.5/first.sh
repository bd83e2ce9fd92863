# PR 49, the new cell's first time on the chip (after chip_kernel_parity.py
# gqa_uneven alone): the cell once untraced at a provisional rate, then the
# sweep of 60 s windows on the first seed (a process's first pass is
# discarded: the first rate twice).
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/records/mimo-v2.5/first.sh
out=chiprun_out/mimo-v2.5; mkdir -p $out
cell=mimo-v2.5.serve-code-agent
t0=$(date +%s)
python3 -m chipbench.run --workload $cell --seed 2147483949 --seconds 51 --trace 0 > $out/first_run.log 2> $out/first_run.err
echo "first run rc=$? in $(( $(date +%s) - t0 )) s"; tail -c 6000 $out/first_run.log; tail -c 2500 $out/first_run.err
t0=$(date +%s)
python3 -m chipbench.tools.sweep --workload $cell --rates ${RATES:-1.6,1.6,2.0,2.4,2.8,3.2} --seconds 60 --seed ${SEED:-7} > $out/sweep_seed${SEED:-7}.log 2> $out/sweep_seed${SEED:-7}.err
echo "sweep rc=$? in $(( $(date +%s) - t0 )) s"; cut -c1-700 $out/sweep_seed${SEED:-7}.log; tail -c 1500 $out/sweep_seed${SEED:-7}.err
