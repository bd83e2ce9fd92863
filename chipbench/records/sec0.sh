set -x
mkdir -p chiprun_out/sec0
env | grep -i -E "jax|xla|tpu" > chiprun_out/sec0/env.txt
python3 -m chipbench.tools.repeat --workload gpt2-125m.train-1chip --runs 10 --seconds 20 --seed0 2147480000 --out chiprun_out/sec0
python3 -m chipbench.tools.repeat --workload resnet50.train-1chip --runs 3 --seconds 20 --seed0 2147480000 --out chiprun_out/sec0
python3 -m chipbench.run --workload gpt2-125m.train-1chip --seed 5 --seconds 20 --trace 1 > chiprun_out/sec0/trace_run.log 2> chiprun_out/sec0/trace_run.err
tail -c 3000 chiprun_out/sec0/trace_run.log; tail -c 2000 chiprun_out/sec0/trace_run.err
python3 -m chipbench.tools.trace_dump .chipbench_trace 6 > chiprun_out/sec0/trace_dump.txt 2>&1
head -c 6000 chiprun_out/sec0/trace_dump.txt
cp $(ls .chipbench_trace/plugins/profile/*/*.xplane.pb | tail -1) chiprun_out/sec0/gpt2_train.xplane.pb
ls -la chiprun_out/sec0/ | head -40; du -sh .jax_cache 2>/dev/null
