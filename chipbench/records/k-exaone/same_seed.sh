# PR 40: is a run that reads serve_ttft_p95_ms high a property of its seed (the
# rotation of the fixed trace) or of the run? The seeds of setB's and setC's
# highest runs and of two ordinary ones, each once more, from .archive_check.
#   chiprun --chips 1 --timeout 1500 -- sh chipbench/records/k-exaone/same_seed.sh
out=$PWD/chiprun_out/k-exaone/same_seed; mkdir -p $out; cell=k-exaone-236b-a23b.serve-mixed-len
cd .archive_check
for seed in 2152483015 2152487015 2147487000 2148487003; do
  python3 -m chipbench.run --workload $cell --seed $seed --seconds 51 --trace 0 > $out/$seed.log 2> $out/$seed.err
  echo "seed $seed rc=$?"; grep '"sweep"' $out/$seed.log | cut -c1-500
done
