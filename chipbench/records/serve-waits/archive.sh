# The final tree AS GIT WOULD COMMIT IT against the parent, as on_chip.sh:
#   git add -A; sh chipbench/records/serve-waits/prepare.sh <parent commit> index    (here)
#   chiprun --chips 1 --timeout 3300 -- sh chipbench/records/serve-waits/archive.sh <base seed> <pairs of cell 2> <pairs of cell 5>
# Untraced pairs of gpt2-125m.serve-chat (the cell whose bound is 1%) and of
# xing4.0-29b-a4b.serve-docqa, then one traced run each of cells 6 and 5 from
# the archive alone (a traced run of serve-chat takes fifteen minutes on
# either side: cell2/ has the pair).
out=$PWD/chiprun_out/pr42/archive; inside=
. "$(dirname "$0")/runs.sh"
pairs gpt2-125m.serve-chat $1 1 $2
pairs xing4.0-29b-a4b.serve-docqa $(($1 + 100)) 1 $3
run change k-exaone-236b-a23b.serve-mixed-len $(($1 + 600)) 1 change.traced
run change xing4.0-29b-a4b.serve-docqa $(($1 + 700)) 1 change.traced
done_runs
