# PR 40, the new cell's first time on the chip: the prefill kernel at other
# blocks, then the cell once untraced and once traced at a provisional rate.
#   chiprun --chips 1 --timeout 2400 -- sh chipbench/records/k-exaone/first.sh
out=chiprun_out/k-exaone; mkdir -p $out
cell=k-exaone-236b-a23b.serve-mixed-len
python3 chip_kernel_parity.py gqa prefill > $out/variants.log 2> $out/variants.err
grep gqa_prefill $out/variants.log | cut -c1-400
python3 -m chipbench.run --workload $cell --seed 2147483999 --seconds 20 --trace 0 > $out/first_run.log 2> $out/first_run.err
echo rc=$?; tail -c 6000 $out/first_run.log; tail -c 2500 $out/first_run.err
python3 -m chipbench.run --workload $cell --seed 2147484999 --seconds 20 --trace 1 > $out/first_traced.log 2> $out/first_traced.err
echo rc=$?; tail -c 6000 $out/first_traced.log; tail -c 2500 $out/first_traced.err
