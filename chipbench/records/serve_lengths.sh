set -x
python3 -m chipbench.tools.repeat --workload gpt2-125m.serve-chat --runs 6 --seconds 51 --seed0 2147480000 --out chiprun_out/serve_len51
python3 -m chipbench.tools.repeat --workload gpt2-125m.serve-chat --runs 6 --seconds 35 --seed0 2147480000 --out chiprun_out/serve_len35
true
