# step_overhead.py in the parent commit and in the change, in turn, five
# times each (parent, change, change, parent, ...), on this machine's CPU.
#   sh chipbench/records/serve-waits/step_overhead.sh <parent commit> [blocks]
set -e
here=$(dirname "$0")
work=$(mktemp -d)
trap 'rm -rf "$work"' EXIT
cp "$here/step_overhead.py" "$work/"
mkdir -p "$work/parent" "$work/change"
git archive "$1" pytorch_distributed_tpu | tar -x -C "$work/parent"
git ls-files -co --exclude-standard pytorch_distributed_tpu | tar -c -T - | tar -x -C "$work/change"
for side in parent change change parent parent change change parent parent change; do
  echo "$side $(PYTHONPATH=$work/$side JAX_PLATFORMS=cpu python3 "$work/step_overhead.py" "${2:-40}" 2>/dev/null)"
done
