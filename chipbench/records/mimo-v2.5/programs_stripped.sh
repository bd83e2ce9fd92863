# The serving programs of the cells named (default: cell 6, whose cache,
# kernels and block PR 49 generalises), lowered through the benchmark's
# warm-up path in the parent commit and in the change with every location
# stripped (programs_stripped.py): every line must be the same.
#   sh chipbench/records/mimo-v2.5/programs_stripped.sh <parent commit> [cell ...]
# run from the root of the change's checkout; as
# ../serve-waits/programs_through_scheduler.sh in everything else.
set -e
here=$(dirname "$0")
parent=$1; shift
work=$(mktemp -d)
trap 'rm -rf "$work"' EXIT
mkdir "$work/tools"
cp "$here/programs_stripped.py" "$here/../serve-waits/programs_through_scheduler.py" "$here/../serve-waits/lowering_shim.py" "$work/tools/"
tree=$work/tree
for side in parent change; do
  rm -rf "$tree"; mkdir -p "$tree"
  if [ $side = parent ]; then
    git archive "$parent" | tar -x -C "$tree"
    git ls-files -co --exclude-standard BENCHMARK.json chipbench | tar -c -T - | tar -x -C "$tree"
  else git ls-files -co --exclude-standard | tar -c -T - | tar -x -C "$tree"; fi
  PYTHONPATH=$tree JAX_PLATFORMS=cpu python3 "$work/tools/programs_stripped.py" "$work/tools" "$tree" ${@:-k-exaone-236b-a23b.serve-mixed-len} 2>"$work/$side.err" > "$work/$side.txt" || { tail -n 30 "$work/$side.err"; exit 1; }
done
diff "$work/parent.txt" "$work/change.txt" && echo IDENTICAL
cat "$work/change.txt"
