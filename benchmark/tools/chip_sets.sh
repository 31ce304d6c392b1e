#!/bin/bash
# Two sets of runs of one cell, the same seeds in both, as the bounds are
# set from:  bash benchmark/tools/chip_sets.sh <cell> <seconds> <seed>...
cell=$1; secs=$2; shift 2
out=chiprun_out/sets/$cell; mkdir -p $out
for set in 1 2; do
  for seed in "$@"; do
    timeout 900 python3 benchmark/run.py --workload $cell --seed $seed --seconds $secs --trace 0 > $out/set$set.$seed.out 2> $out/set$set.$seed.err
    echo "set$set seed $seed rc=$? $(tail -n 1 $out/set$set.$seed.out | cut -c1-420)"
    grep -E "^\[check\]|FAIL|Error" $out/set$set.$seed.err | grep -v " ok$" | head -n 5
  done
done
python3 benchmark/tools/spread.py $out
