# PR 40: the knee of k-exaone-236b-a23b.serve-mixed-len, on the finished change
# (the parent cannot run the cell). The first rate is given twice: a process's
# first pass reads high (PERF.md section 7, From PR 33 (3)) and is discarded.
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/records/k-exaone/sweep.sh "1.5,1.5,2.0,2.5,3.0,3.5,4.0"
out=chiprun_out/k-exaone; mkdir -p $out
python3 -m chipbench.tools.sweep --workload k-exaone-236b-a23b.serve-mixed-len --rates "$1" --seconds 30 --seed 7 > $out/sweep.log 2> $out/sweep.err
echo rc=$?; cut -c1-700 $out/sweep.log; tail -c 2000 $out/sweep.err
