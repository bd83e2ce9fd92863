# After the inputs of a step are released right after the call (engine.py,
# below the call expressions): the compile cache's keys of cells 5 and 6 once
# more, on the chip. One untraced pair each, the PARENT first: the change's
# `setup` line right after it must read as a second run of the parent's
# (`programs` == `cache_hits`). Cell 2's are in steady2/ and steady3/.
#   git add -A; sh chipbench/records/serve-waits/prepare.sh <parent commit> index    (here)
#   chiprun --chips 1 --timeout 1800 -- sh chipbench/records/serve-waits/keys_again.sh <base seed>
out=$PWD/chiprun_out/pr42/keys_again; inside=
. "$(dirname "$0")/runs.sh"
pairs k-exaone-236b-a23b.serve-mixed-len $1 1 1
pairs xing4.0-29b-a4b.serve-docqa $(($1 + 2)) 1 1
done_runs
