# The serving programs of cells 2, 5 and 6, lowered through the benchmark's
# warm-up path (programs_through_scheduler.py) in the parent commit and in
# the change (no chip: a described v5e): every line must be the same.
#   sh chipbench/records/serve-waits/programs_through_scheduler.sh <parent commit>
# run from the root of the change's checkout. Both trees are unpacked, one
# after the other, into the SAME directory (a Mosaic kernel's serialized
# module carries its source files' full paths), one that `mktemp -d` makes
# under $TMPDIR for this run alone; the helpers and both texts lie beside
# it, and all of it goes at the end. The PARENT runs under the change's
# BENCHMARK.json and chipbench/, as the driver's check lays them over it.
set -e
here=$(dirname "$0")
work=$(mktemp -d)
trap 'rm -rf "$work"' EXIT
cp "$here/programs_through_scheduler.py" "$here/lowering_shim.py" "$work/"
tree=$work/tree
for side in parent change; do
  rm -rf "$tree"; mkdir -p "$tree"
  if [ $side = parent ]; then
    git archive "$1" | tar -x -C "$tree"
    git ls-files -co --exclude-standard BENCHMARK.json chipbench | tar -c -T - | tar -x -C "$tree"
  else git ls-files -co --exclude-standard | tar -c -T - | tar -x -C "$tree"; fi
  PYTHONPATH=$tree JAX_PLATFORMS=cpu python3 "$work/programs_through_scheduler.py" "$tree" 2>"$work/$side.err" > "$work/$side.txt" || { tail -n 30 "$work/$side.err"; exit 1; }
done
diff "$work/parent.txt" "$work/change.txt" && echo IDENTICAL
cat "$work/change.txt"
