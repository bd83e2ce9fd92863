# PR 45, after the driver's first benchmark check could not tell
# (`serve_tpot_p50_ms` in gpt2-125m.serve-chat: the middle half of six runs
# spread 0.0356 ms at the parent, 0.0445 ms with the change, bound 0.0391 ms):
# untraced pairs of that cell alone, parent and change in turn on ONE machine
# from ONE path, every pair on a seed of its own, which side goes first
# alternating (PR 42's chipbench/records/serve-waits/runs.sh and steady.py).
#   sh chipbench/records/serve-waits/prepare.sh 3fc1df9 index      (here)
#   chiprun --chips 1 --timeout 2000 -- sh chipbench/records/kimi-linear/steady.sh <tag> <base seed> <pairs>
#   python3 chipbench/records/serve-waits/steady.py chiprun_out/kimi-linear/<tag>
out=$PWD/chiprun_out/kimi-linear/$1; inside=
. chipbench/records/serve-waits/runs.sh
pairs gpt2-125m.serve-chat $2 1 $3
done_runs
