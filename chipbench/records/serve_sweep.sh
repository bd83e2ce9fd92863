set -x
mkdir -p chiprun_out/sweep
python3 -m chipbench.tools.sweep --workload gpt2-125m.serve-chat --rates 6,8,9,10,11,12,14 --seconds 30 --seed 7 > chiprun_out/sweep/sweep.log 2> chiprun_out/sweep/sweep.err
tail -c 5000 chiprun_out/sweep/sweep.log; tail -c 1500 chiprun_out/sweep/sweep.err
python3 -m chipbench.run --workload gpt2-125m.serve-chat --seed 2147483999 --seconds 30 --trace 0 > chiprun_out/sweep/run0.log 2> chiprun_out/sweep/run0.err
tail -c 3000 chiprun_out/sweep/run0.log; tail -c 1500 chiprun_out/sweep/run0.err
python3 -m chipbench.run --workload gpt2-125m.serve-chat --seed 2147483999 --seconds 30 --trace 1 > chiprun_out/sweep/run1.log 2> chiprun_out/sweep/run1.err
tail -c 4000 chiprun_out/sweep/run1.log; tail -c 1500 chiprun_out/sweep/run1.err
python3 -m chipbench.tools.trace_dump .chipbench_trace 3 2>/dev/null | cut -c1-300 > chiprun_out/sweep/trace_dump.txt
true
