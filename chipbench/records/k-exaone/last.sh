sh chipbench/records/k-exaone/final.sh
sh chipbench/records/k-exaone/pairs.sh gpt2-125m.serve-chat xing4.0-29b-a4b.serve-docqa
