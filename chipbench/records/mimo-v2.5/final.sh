# PR 49, the final tree, from the files git would commit (.archive_check holds
# `git archive $(git write-tree)`, made before the call): the limits' readings
# over all eight degraded references (exit 1 expected: limits_all.py), a
# second set of six seeds, two traced seeds.
#   chiprun --chips 1 --timeout 3550 -- sh chipbench/records/mimo-v2.5/final.sh
export FROM=.archive_check
ALL=1 sh chipbench/records/mimo-v2.5/limits.sh 2147483749 _final 2>&1 | cut -c1-300 | grep -v '^{"reading".*near_tie' 
sh chipbench/records/mimo-v2.5/sets.sh setB 2147481049 6 2147483852 2147483853
