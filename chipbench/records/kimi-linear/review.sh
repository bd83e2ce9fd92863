# PR 45 after review (REVIEW.md): the reference's programs are never written to
# the compile cache (families/kimi_linear.py: NEVER_CACHED_S), so
# (1) two runs of the cell, two seeds, on a compile cache that STARTS EMPTY
#     (JAX_COMPILATION_CACHE_DIR names a new directory): what a run writes, by
#     name, and what the reference's own compiles cost a run (`reference_s` on
#     the check line; the second run hits every serving program and still
#     compiles the reference);
# (2) the knee's seed 7 again at 3.0 (first pass, discarded), 3.5 and 4.0/s with
#     60 s windows (the first sweep had 30 s windows and no 3.5);
# (3) tools/check_limits_knobs.py on that same cache, listed by name after it:
#     the degraded references' programs are not written either.
#   chiprun --chips 1 --timeout 3300 -- sh chipbench/records/kimi-linear/review.sh
out=$PWD/chiprun_out/kimi-linear/review; mkdir -p $out
cell=kimi-linear-48b-a3b.serve-long-answer
fresh=$PWD/.scratch/jax_cache_fresh; rm -rf $fresh; mkdir -p $fresh
by_name() {
  echo "dir=$1 $(du -sm $1 | cut -f1) MiB $(ls $1 | wc -l) files"
  ls -l $1 | awk '{print $5, $9}' | sed -E 's/-[0-9a-f]{20,}.*$//' | awk '$2 {b[$2]+=$1; n[$2]++} END {for (k in b) printf "%.1f MiB in %d  %s\n", b[k]/1048576, n[k], k}' | sort -rn | head -30
}
n=0
for seed in 2148487003 2149487009; do
  n=$((n + 1)); t0=$(date +%s)
  JAX_COMPILATION_CACHE_DIR=$fresh python3 -m chipbench.run --workload $cell --seed $seed --seconds 51 --trace 0 > $out/fresh$n.log 2> $out/fresh$n.err
  echo "fresh cache, run $n seed $seed: rc=$? in $(( $(date +%s) - t0 )) s"
  grep -h '"event": "setup"' $out/fresh$n.log | cut -c1-150
  grep -h '"event": "check"' $out/fresh$n.log | cut -c1-600
  tail -n 1 $out/fresh$n.log | cut -c1-500; tail -c 600 $out/fresh$n.err
  by_name $fresh | tee $out/fresh_cache_after$n.txt
done
t0=$(date +%s)
JAX_COMPILATION_CACHE_DIR=$fresh python3 -m chipbench.tools.sweep --workload $cell --rates 3,3.5,4 --seconds 60 --seed 7 > $out/sweep_seed7_60s.log 2> $out/sweep_seed7_60s.err
echo "sweep rc=$? in $(( $(date +%s) - t0 )) s"; cut -c1-700 $out/sweep_seed7_60s.log; tail -c 800 $out/sweep_seed7_60s.err
t0=$(date +%s)
JAX_COMPILATION_CACHE_DIR=$fresh python3 -m chipbench.tools.check_limits_knobs --workload $cell --seed 2150487013 --seconds 20 > $out/limits_readings_review.log 2> $out/limits_readings_review.err
echo "limits rc=$? in $(( $(date +%s) - t0 )) s"; grep -h 'passes_the_rule\|"ok"' $out/limits_readings_review.log | cut -c1-300; tail -c 800 $out/limits_readings_review.err
by_name $fresh > $out/fresh_cache_after_limits.txt
echo "the fresh cache, after the second run and after the limits' readings:"; diff $out/fresh_cache_after2.txt $out/fresh_cache_after_limits.txt && echo "no entry added"
