# GPT-2's and Xing4.0's serving programs, lowered in the parent commit and in
# the change (no chip: a described v5e): every line must be the same.
#   sh chipbench/records/k-exaone-compact/programs_identical.sh <parent commit>
# run from the root of the change's checkout. Both trees are unpacked, one
# after the other, into the SAME directory (a Mosaic kernel's serialized
# module carries its source files' full paths), one that `mktemp -d` makes
# under $TMPDIR for this run alone; the helper and both texts lie beside it,
# and all of it goes at the end.
set -e
work=$(mktemp -d)
trap 'rm -rf "$work"' EXIT
cp "$(dirname "$0")/programs_text.py" "$work/programs_text.py"
tree=$work/tree
for side in parent change; do
  rm -rf "$tree"; mkdir -p "$tree"
  if [ $side = parent ]; then git archive "$1" | tar -x -C "$tree"
  else git ls-files -co --exclude-standard | tar -c -T - | tar -x -C "$tree"; fi
  PYTHONPATH=$tree JAX_PLATFORMS=cpu python3 "$work/programs_text.py" "$tree" 2>/dev/null > "$work/$side.txt"
done
diff "$work/parent.txt" "$work/change.txt" && echo IDENTICAL
cat "$work/change.txt"
