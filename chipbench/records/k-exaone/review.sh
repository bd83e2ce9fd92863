# PR 40 after its review, from the files git would commit (.archive_check
# holds `git archive $(git write-tree)`, made before the call): the knee's
# sweep on a second seed with 2.75/s in the list (the first rate twice: a
# process's first pass is discarded), three new seeds untraced and one traced
# run of the cell with the reference cut to two widths, and what each step
# adds to the compile cache (names and bytes of its entries).
#   chiprun --chips 1 --timeout 2700 -- sh chipbench/records/k-exaone/review.sh
out=$PWD/chiprun_out/k-exaone/review; cell=k-exaone-236b-a23b.serve-mixed-len
mkdir -p $out; cd .archive_check
cache=${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}
entries() {   # bytes and name of every entry, and their sum
  ls -l "$cache" 2>/dev/null | awk 'NR>1 {print $5, $9}' | sort -k2 > $out/cache_$1.txt
  echo "cache $1: $(wc -l < $out/cache_$1.txt) files, $(awk '{s+=$1} END {printf "%.1f", s/1048576}' $out/cache_$1.txt) MiB"
}
entries 0_before
python3 -m chipbench.tools.sweep --workload $cell --rates "2.0,2.0,2.25,2.5,2.75,3.0" --seconds 30 --seed 11 > $out/sweep_seed11.log 2> $out/sweep_seed11.err
echo "sweep rc=$?"; cut -c1-330 $out/sweep_seed11.log
entries 1_after_sweep
for seed in 2147489000 2147489001 2147489002; do
  t0=$(date +%s)
  python3 -m chipbench.run --workload $cell --seed $seed --seconds 51 --trace 0 > $out/run.$seed.log 2> $out/run.$seed.err
  echo "seed $seed: rc=$? in $(( $(date +%s) - t0 )) s"; grep '"event": "check"' $out/run.$seed.log | cut -c1-420; tail -n 1 $out/run.$seed.log | cut -c1-330
  entries 2_after_$seed
done
t0=$(date +%s)
python3 -m chipbench.run --workload $cell --seed 2147400003 --seconds 51 --trace 1 > $out/traced.log 2> $out/traced.err
echo "traced: rc=$? in $(( $(date +%s) - t0 )) s; unread lines: $(grep -c unread $out/traced.log)"; tail -n 1 $out/traced.log | cut -c1-2500
entries 3_after_traced
cd $out; prev=cache_0_before.txt
for f in cache_1_after_sweep.txt cache_2_after_2147489000.txt cache_2_after_2147489001.txt cache_2_after_2147489002.txt cache_3_after_traced.txt; do
  echo "== entries in $f that $prev lacks"; awk 'NR==FNR {seen[$2]=1; next} !($2 in seen)' $prev $f | cut -c1-120; prev=$f
done
