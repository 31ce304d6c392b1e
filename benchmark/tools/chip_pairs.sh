#!/bin/bash
# Parent and change in turn on one chip, sides alternating, one run a side
# and seed; then one --trace 1 run a side at the last seed + 1:
#   bash benchmark/tools/chip_pairs.sh <parent checkout> <cell> <seconds> <seed>...
# The parent is a `git archive` of its commit unpacked into a directory
# that .gitignore lists (build/parent); the change is the tree this runs from.
parent=$1; cell=$2; secs=$3; shift 3
here=$(pwd); out=$here/chiprun_out/pairs/$cell; mkdir -p $out
one() {  # side, seed, trace
  local dir=$here; [ $1 = parent ] && dir=$parent
  local tag=$1.$2.t$3
  (cd $dir && timeout 1500 python3 benchmark/run.py --workload $cell --seed $2 \
     --seconds $secs --trace $3 > $out/$tag.out 2> $out/$tag.err)
  echo "$tag rc=$? $(tail -n 1 $out/$tag.out | cut -c1-900)"
  grep -E "first losses|^\[check\]|setup_s|FAIL|Error" $out/$tag.err | head -n 12
}
n=0
for seed in "$@"; do
  if [ $((n % 2)) = 0 ]; then one parent $seed 0; one change $seed 0
  else one change $seed 0; one parent $seed 0; fi
  n=$((n + 1))
done
one parent $((seed + 1)) 1; one change $((seed + 1)) 1
