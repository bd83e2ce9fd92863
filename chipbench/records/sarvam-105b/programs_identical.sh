# The serving programs of the cells named (default: all five serving cells
# that were there before PR 54), lowered through the benchmark's warm-up
# path in the parent commit and in the change for a described v5e (no chip):
# every line must be the same, `key=` (what the compile cache hashes: op
# locations stripped, the kernels' recorded call stacks kept) and `strict=`
# (every location) alike.
#   sh chipbench/records/sarvam-105b/programs_identical.sh <parent commit> [cell ...]
# run from the root of the change's checkout; as
# ../serve-waits/programs_through_scheduler.sh in everything else (which
# takes no cell names: it lowers cells 2, 5 and 6).
set -e
here=$(dirname "$0")
parent=$1; shift
work=$(mktemp -d)
trap 'rm -rf "$work"' EXIT
cp "$here/../serve-waits/programs_through_scheduler.py" "$here/../serve-waits/lowering_shim.py" "$work/"
tree=$work/tree
for side in parent change; do
  rm -rf "$tree"; mkdir -p "$tree"
  if [ $side = parent ]; then
    git archive "$parent" | tar -x -C "$tree"
    git ls-files -co --exclude-standard BENCHMARK.json chipbench | tar -c -T - | tar -x -C "$tree"
  else git ls-files -co --exclude-standard | tar -c -T - | tar -x -C "$tree"; fi
  PYTHONPATH=$tree JAX_PLATFORMS=cpu python3 "$work/programs_through_scheduler.py" "$tree" ${@:-gpt2-125m.serve-chat xing4.0-29b-a4b.serve-docqa k-exaone-236b-a23b.serve-mixed-len kimi-linear-48b-a3b.serve-long-answer mimo-v2.5.serve-code-agent} 2>"$work/$side.err" > "$work/$side.txt" || { tail -n 30 "$work/$side.err"; exit 1; }
done
diff "$work/parent.txt" "$work/change.txt" && echo IDENTICAL
cat "$work/change.txt"
