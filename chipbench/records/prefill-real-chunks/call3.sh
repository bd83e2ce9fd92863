# PR 51, third call: cell 8's pairs with a traced pair, one pair of cell 7,
# then cell 6's kernel events.
#   chiprun --chips 1 --timeout 3550 -- sh chipbench/records/prefill-real-chunks/call3.sh
here=chipbench/records/prefill-real-chunks
env TRACED=mimo-v2.5.serve-code-agent sh $here/pairs.sh 2147484200 mimo-v2.5.serve-code-agent=5 kimi-linear-48b-a3b.serve-long-answer=1
sh $here/kernel_events.sh 2147484102
