# PR 40: at 2.0/s (0.8 of the knee) one run in twelve read serve_ttft_p95_ms 29%
# high (two long prompts admitted in one scheduler step) and setB spread 10.8%,
# over the 5% a new cell may show: six seeds untraced and one traced run at
# each of two lower rates, from the files git would commit (.archive_check
# holds `git archive $(git write-tree)`, made before the call; the rate is
# set in its copy of the traffic file).
#   chiprun --chips 1 --timeout 3550 -- sh chipbench/records/k-exaone/rates.sh 1.75 1.5
# (the two sets were then moved to records/superseded/k-exaone/: they are not
# sets of the cell as its traffic file has it)
out=$PWD/chiprun_out/k-exaone; cell=k-exaone-236b-a23b.serve-mixed-len
cd .archive_check
file=chipbench/traffic/serve-mixed-len.json; was=2.0
for rate in "$@"; do
  sed -i "s/\"rate_per_s\": $was\$/\"rate_per_s\": $rate/" $file; was=$rate
  grep -n '"rate_per_s"' $file
  python3 -m chipbench.tools.repeat --workload $cell --runs 6 --seconds 51 --seed0 2147485000 --out $out/rate_$rate 2>&1 | cut -c1-330
  t0=$(date +%s)
  python3 -m chipbench.run --workload $cell --seed 2147400001 --seconds 51 --trace 1 > $out/rate_$rate/traced.log 2> $out/rate_$rate/traced.err
  echo "rate $rate traced: rc=$? in $(( $(date +%s) - t0 )) s; unread lines: $(grep -c unread $out/rate_$rate/traced.log)"; tail -n 1 $out/rate_$rate/traced.log | cut -c1-1200
done
