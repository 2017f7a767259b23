# The bound measurement of one cell, run on the chip:
#   chiprun --timeout 2400 -- bash benchmark/sets.sh rs83.encode sets_enc 3000002000
# Each line of chiprun_out/<out>/sets.jsonl holds one run: set, seed, exit code, wall ms, result line.
# usage: bash benchmark/sets.sh <cell> <out> <seed0> [runs per set, 6]
# two sets of the same seeds, then 3 traced runs
cell=$1; out=chiprun_out/$2; s0=$3; n=${4:-6}
secs=$(python3 -c 'import json; print(json.load(open("BENCHMARK.json"))["run_seconds"])')
mkdir -p $out
for set in 1 2; do for i in $(seq 1 $n); do
  s=$((s0 + i)); t0=$(date +%s%3N)
  python3 benchmark/run.py --workload $cell --seed $s --seconds $secs --trace 0 > $out/run.out 2> $out/err_${set}_$i.txt
  rc=$?; t1=$(date +%s%3N)
  echo "{\"set\": $set, \"seed\": $s, \"rc\": $rc, \"wall\": $((t1 - t0)), \"line\": $(tail -1 $out/run.out)}" | tee -a $out/sets.jsonl | cut -c1-400
done; done
for i in 11 12 13; do
  s=$((s0 + i)); t0=$(date +%s%3N)
  python3 benchmark/run.py --workload $cell --seed $s --seconds $secs --trace 1 > $out/run.out 2> $out/err_tr_$i.txt
  rc=$?; t1=$(date +%s%3N)
  echo "{\"traced\": 1, \"seed\": $s, \"rc\": $rc, \"wall\": $((t1 - t0)), \"line\": $(tail -1 $out/run.out)}" | tee -a $out/sets.jsonl | cut -c1-1500
done
