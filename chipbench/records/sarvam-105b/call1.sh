# PR 54, the first call to the chip, one command (chips were scarce):
#   chiprun --chips 1 --timeout 3600 -- sh chipbench/records/sarvam-105b/call1.sh
# from the root of the change's checkout, the parent commit unpacked in
# .parent/ (git archive). Everything that does not depend on the cell's
# rate: the kernels alone, one run of the cell, the PARENT's clean failure
# under the change's BENCHMARK.json and chipbench/, the sweep, the readings
# the limits lie between. Stops where a step that the later ones stand on
# fails.
out=chiprun_out/sarvam; mkdir -p $out
cell=sarvam-105b.serve-doc-sessions
python3 chip_kernel_parity.py latent_paged > $out/kernel_parity_latent_paged.log 2> $out/parity.err
rc=$?; echo parity_rc=$rc; cat $out/kernel_parity_latent_paged.log | cut -c1-1200; tail -n 5 $out/parity.err | cut -c1-400
[ $rc = 0 ] || exit 1
python3 -m chipbench.run --workload $cell --seed 7 --seconds 51 --trace 0 > $out/first_run.log 2> $out/first_run.err
rc=$?; echo first_run_rc=$rc; tail -n 6 $out/first_run.log | cut -c1-2500; tail -n 12 $out/first_run.err | cut -c1-500
[ $rc = 0 ] || exit 1
cp -r BENCHMARK.json chipbench .parent/
( cd .parent; t0=$(date +%s); python3 -m chipbench.run --workload $cell --seed 7 --seconds 51 --trace 0 > ../$out/parent_fails.log 2> ../$out/parent_fails.err; echo parent_rc=$? after $(( $(date +%s) - t0 )) s; tail -n 2 ../$out/parent_fails.log | cut -c1-300; tail -n 3 ../$out/parent_fails.err | cut -c1-300 )
python3 chipbench/records/sarvam-105b/sweep.py --rates 1.0,1.0,1.5,2.0,2.5,3.0,3.5 --seconds 40 --seed 7  # at 4,096 pages, as the traffic file then had > $out/sweep_seed7.log 2> $out/sweep_seed7.err
echo sweep_rc=$?; cut -c1-1400 $out/sweep_seed7.log; tail -n 5 $out/sweep_seed7.err | cut -c1-400
python3 chipbench/records/sarvam-105b/limits.py --seed 2147483701 --seconds 30 > $out/limits_readings.log 2> $out/limits_readings.err
echo limits_rc=$?; grep -v '"near_tie_0.0"' $out/limits_readings.log | cut -c1-600; grep '"reading"' $out/limits_readings.log | cut -c1-1500; tail -n 5 $out/limits_readings.err | cut -c1-400
