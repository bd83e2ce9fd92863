# PR 54, the fourth call: warm set-up of cells 5, 6 and 7 in parent / change
# pairs on one machine (chip_setup_phases.py; the parent as `git archive`
# left it in .parent/, the change from .archive_check/ = git archive of the
# tree that will be committed), who goes first alternating; then the new
# cell once more from .archive_check/: the committed files are enough.
#   chiprun --chips 1 --timeout 3600 -- sh chipbench/records/sarvam-105b/call4.sh [pairs]
out=$PWD/chiprun_out/sarvam/setup_pairs; mkdir -p $out
pairs=${1:-1}
run() {  # side cell seed n
  ( cd $1; python3 chip_setup_phases.py --workload $2 --seed $3 --seconds 5 --trace 0 > $out/$2.$4.$1.log 2> $out/$2.$4.$1.err
    echo "$2 pair $4 $1 rc=$? $(tail -n 40 $out/$2.$4.$1.log | grep -o '"setup_s": {[^}]*}' | tail -n 1) $(grep -o '"correct": [a-z]*' $out/$2.$4.$1.log | tail -n 1)" )
}
for cell in kimi-linear-48b-a3b.serve-long-answer xing4.0-29b-a4b.serve-docqa; do   # cell 6 left out: the time ran short
  # one unrecorded run a side first: whichever programs the machine's cache lacks are compiled here
  run .parent $cell 2147483900 warm; run .archive_check $cell 2147483900 warm
  for n in $(seq 1 $pairs); do
    if [ $((n % 2)) = 1 ]; then run .parent $cell $((2147483900 + n)) $n; run .archive_check $cell $((2147483900 + n)) $n
    else run .archive_check $cell $((2147483900 + n)) $n; run .parent $cell $((2147483900 + n)) $n; fi
  done
done
( cd .archive_check; python3 -m chipbench.run --workload sarvam-105b.serve-doc-sessions --seed 2147483831 --seconds 51 --trace 0 > $out/../archive_run.log 2> $out/../archive_run.err; echo archive_rc=$?; tail -n 1 $out/../archive_run.log | cut -c1-600 )
