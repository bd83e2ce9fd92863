#!/bin/sh
# PR 48, step (a)/(b): the kernel alone at the cells' shapes, then where a
# set-up of cell 6 goes, at the parent (.parent/, `git archive` of 16c3d1a)
# and with the change: one cold run (empty compile cache), two warm ones
# under chip_setup_phases.py (JAX's monitoring events summed by phase).
# As it ran: the machine sets JAX_COMPILATION_CACHE_DIR, so `rm -rf .jax_cache`
# emptied nothing: the parent's first run was cold because the machine came
# empty, the change's found 20 of its 28 programs there (pairs.sh does it right).
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/records/grouped-matmul/setup_first.sh
out=$PWD/chiprun_out/grouped-matmul; mkdir -p $out
cell=k-exaone-236b-a23b.serve-mixed-len
python3 chip_kernel_parity.py grouped sweep > $out/kernel_parity_grouped.log 2>$out/kernel_parity_grouped.err
echo "parity rc=$?"; tail -n 1 $out/kernel_parity_grouped.log
seed=4810001
for tree in parent change; do
  if [ $tree = parent ]; then cd .parent; else cd ..; fi
  rm -rf .jax_cache
  for run in cold warm1 warm2; do
    seed=$((seed + 1))
    python3 chip_setup_phases.py --workload $cell --seed $seed --seconds 51 --trace 0 \
      > $out/phases_${tree}_${run}.log 2>$out/phases_${tree}_${run}.err
    echo "$tree $run rc=$? $(tail -n 2 $out/phases_${tree}_${run}.log | head -n 1 | cut -c1-400)"
  done
done
