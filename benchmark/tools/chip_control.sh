#!/bin/bash
# The numbers `correct` compares, sound and control, on both sides:
#   bash benchmark/tools/chip_control.sh <parent checkout|-> <cell> <seconds> <seeds,comma>
parent=$1; cell=$2; secs=$3; seeds=$4
here=$(pwd); out=$here/chiprun_out/control; mkdir -p $out
for side in parent change; do
  dir=$here; [ $side = parent ] && dir=$parent
  [ $dir = - ] && continue
  (cd $dir && timeout 1800 python3 benchmark/control.py --workload $cell \
     --seeds $seeds --seconds $secs > $out/$side.$cell.out 2> $out/$side.$cell.err)
  echo "$side control rc=$?"; cat $out/$side.$cell.out
  grep -E "first losses|FAIL|Error" $out/$side.$cell.err | head -n 12
done
