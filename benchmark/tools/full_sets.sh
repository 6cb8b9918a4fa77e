#!/bin/sh
# The runs a cell's bounds and breakdown are read from:
#   full_sets.sh <cell> <seconds> <out file> [seeds...]
# Two sets, each running every seed once (the same seeds in both, every
# run a new process), then one traced run on a seed of its own.  One
# result line a run in <out file>, every run's whole output in
# <out file>.log; `python3 -m benchmark.tools.spreads <out file>` prints
# the medians and spreads.
cell=$1; seconds=$2; out=$3; shift 3
seeds=${*:-"2147483659 1200000017 37 900000011 52001 3000000019"}
mkdir -p "$(dirname "$out")"
for set in 1 2; do
  for seed in $seeds; do
    python3 -m benchmark.run --workload "$cell" --seed "$seed" --seconds "$seconds" --trace 0 >"$out.last" 2>>"$out.err"
    cat "$out.last" >>"$out.log"
    line=$(tail -n 1 "$out.last")
    echo "{\"set\": $set, \"seed\": $seed, \"line\": $line}" >> "$out"
  done
done
python3 -m benchmark.run --workload "$cell" --seed 4000000007 --seconds "$seconds" --trace 1 >"$out.last" 2>>"$out.err"
cat "$out.last" >>"$out.log"
echo "{\"set\": 0, \"seed\": 4000000007, \"line\": $(tail -n 1 "$out.last")}" >> "$out.traced"
python3 -m benchmark.tools.spreads "$out"
