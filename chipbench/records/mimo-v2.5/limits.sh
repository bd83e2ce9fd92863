# PR 49: the sweep on the second seed (first pass discarded), then the
# readings the cell's limits lie between (the program's tokens and the eight
# degraded references, each scored under the plain float32 reference).
#   RATES=... chiprun --chips 1 --timeout 3500 -- sh chipbench/records/mimo-v2.5/limits.sh [limits seed] [suffix]
# ALL=1 reads the two of NOT_TOLD_APART_ON_THE_CHIP too (limits_all.py: exit
# 1 is then expected); FROM=.archive_check runs the files git would commit.
out=$PWD/chiprun_out/mimo-v2.5; mkdir -p $out
cd ${FROM:-.}
cell=mimo-v2.5.serve-code-agent
if [ -n "$RATES" ]; then
  t0=$(date +%s)
  python3 -m chipbench.tools.sweep --workload $cell --rates $RATES --seconds 60 --seed 11 > $out/sweep_seed11.log 2> $out/sweep_seed11.err
  echo "sweep rc=$? in $(( $(date +%s) - t0 )) s"; cut -c1-700 $out/sweep_seed11.log; tail -c 1000 $out/sweep_seed11.err
fi
t0=$(date +%s)
if [ -n "${ALL:-}" ]; then tool="chipbench/records/mimo-v2.5/limits_all.py"; else tool="-m chipbench.tools.check_limits_knobs"; fi
PYTHONPATH=$PWD python3 $tool --workload $cell --seed ${1:-2147483749} --seconds 20 > $out/limits_readings${2:-}.log 2> $out/limits_readings${2:-}.err
echo "limits rc=$? in $(( $(date +%s) - t0 )) s"; cut -c1-1400 $out/limits_readings${2:-}.log; tail -c 1500 $out/limits_readings${2:-}.err
