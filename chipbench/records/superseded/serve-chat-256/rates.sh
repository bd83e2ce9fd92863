# PR 46, after machine 1's sets at 56/s missed (serve_ttft_p95_ms spread
# 3.4-10.3% over six runs, serve_tpot_p50_ms 2.0-3.1%): is the cell steadier
# lower on its curve? Six untraced runs (tools/repeat.py, 51 s) at each rate
# given, on ONE machine; the rate is written into the machine's own copy of the
# traffic file (thrown away with the machine), nothing else differs.
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/records/superseded/serve-chat-256/rates.sh <tag> <seed0> <rate> [<rate> ...]
# It REWRITES chipbench/traffic/serve-chat.json: only ever inside a chip machine's
# copy of the repo (which has no .git and is thrown away), never in a checkout.
[ -e .git ] && { echo "rates.sh rewrites the tracked traffic file: run it through chiprun only" >&2; exit 2; }
tag=$1; seed0=$2; shift 2
cell=gpt2-125m.serve-chat
for rate in "$@"; do
  sed -i "0,/\"rate_per_s\": [0-9.]*/s//\"rate_per_s\": $rate/" chipbench/traffic/serve-chat.json
  grep rate_per_s chipbench/traffic/serve-chat.json
  python3 -m chipbench.tools.repeat --workload $cell --runs 6 --seconds 51 --seed0 $seed0 --out chiprun_out/serve-chat-loaded/$tag/rate_$rate 2>&1 | cut -c1-700
done
