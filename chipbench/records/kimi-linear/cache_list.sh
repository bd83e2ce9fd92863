# PR 45: what the machine's compile cache holds after this PR's calls, by bytes and name.
#   chiprun --chips 1 --timeout 300 -- sh chipbench/records/kimi-linear/cache_list.sh
out=chiprun_out/kimi-linear; mkdir -p $out
dir=${JAX_COMPILATION_CACHE_DIR:-/root/.cache/chiprun/jax}
echo "dir=$dir"; du -sm $dir; ls $dir | wc -l
ls -l $dir | awk '{print $5, $9}' | sed -E 's/-[0-9a-f]{20,}.*$//' | awk '{b[$2]+=$1; n[$2]++} END {for (k in b) printf "%.1f MiB in %d  %s\n", b[k]/1048576, n[k], k}' | sort -rn | head -40 | tee $out/cache_after_final.txt
