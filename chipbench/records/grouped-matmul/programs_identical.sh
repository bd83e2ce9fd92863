# PR 48: the serving programs of gpt2-125m.serve-chat (the one old serving
# cell whose model has no experts), lowered for a described v5e in the parent
# commit and in the change, WITH their debug information: every line must be
# the same (no file they trace is touched). Xing4.0's programs, which the
# same helper lowers, differ by design (the kernel where ragged_dot stood):
# their lines are printed, not compared. The training cells trace no file
# this PR touches.
#   sh chipbench/records/grouped-matmul/programs_identical.sh <parent commit>
# run from the root of the change's checkout (no chip, two minutes).
set -e
work=$(mktemp -d)
trap 'rm -rf "$work"' EXIT
cp "$(dirname "$0")/../k-exaone/programs_text.py" "$work/programs_text.py"
tree=$work/tree
for side in parent change; do
  rm -rf "$tree"; mkdir -p "$tree"
  if [ $side = parent ]; then git archive "$1" | tar -x -C "$tree"
  else git ls-files -co --exclude-standard | while read f; do [ -e "$f" ] && echo "$f"; done | tar -c -T - | tar -x -C "$tree"; fi
  PYTHONPATH=$tree JAX_PLATFORMS=cpu python3 "$work/programs_text.py" "$tree" 2>/dev/null > "$work/$side.txt"
done
grep '^gpt2' "$work/parent.txt" > "$work/parent.gpt2"
grep '^gpt2' "$work/change.txt" > "$work/change.gpt2"
diff "$work/parent.gpt2" "$work/change.gpt2" && echo IDENTICAL
echo "-- parent"; cat "$work/parent.txt"; echo "-- change"; cat "$work/change.txt"
