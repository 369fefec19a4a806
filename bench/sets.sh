#!/bin/bash
# Measure a cell as the bounds ask: two sets of runs on the same seeds, then
# traced runs. Each run is its own process, as in a check.
#   bash bench/sets.sh <cell> <seconds> <seed,seed,...> <traced seed,...> <out dir>
cell=$1; secs=$2; seeds=$3; traced=$4; out=$5/$cell
mkdir -p "$out"
for set in A B; do
  for s in ${seeds//,/ }; do
    python3 bench/run.py --workload "$cell" --seed "$s" --seconds "$secs" \
      --trace 0 2> "$out/err.$set.$s.txt" | tail -1 > "$out/run.$set.$s.json"
    echo "$set $s rc ${PIPESTATUS[0]} $(cut -c1-400 "$out/run.$set.$s.json")"
  done
done
for s in ${traced//,/ }; do
  python3 bench/run.py --workload "$cell" --seed "$s" --seconds "$secs" \
    --trace 1 --out "$out" 2> "$out/err.T.$s.txt" | tail -1 > "$out/run.T.$s.json"
  echo "T $s rc ${PIPESTATUS[0]} $(cut -c1-600 "$out/run.T.$s.json")"
done
