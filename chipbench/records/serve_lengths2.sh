set -x
python3 -m chipbench.tools.repeat --workload gpt2-125m.serve-chat --runs 4 --seconds 35 --seed0 2147480000 --out chiprun_out/serve_rot35
python3 -m chipbench.tools.repeat --workload gpt2-125m.serve-chat --runs 4 --seconds 20 --seed0 2147480000 --out chiprun_out/serve_rot20
true
