#!/bin/sh
# What PERF.md's PR 40 entry reports of the four-chip cell, in one chip
# call, from the committed files alone:
#   mkdir -p .scratch/archive && git archive $(git write-tree) | tar -x -C .scratch/archive
#   mkdir -p .scratch/parent && git archive <parent> | tar -x -C .scratch/parent
#   cp BENCHMARK.json .scratch/parent/ && cp -r chipbench/. .scratch/parent/chipbench/
#   chiprun --chips 4 --timeout 1500 -- sh chipbench/tests/chip_rb3d.sh .scratch/archive .scratch/parent [warm runs] [first seed] [budget s]
# The parent's run of the cell (it refuses at once: the manifest's message,
# exit code 1), then rb3d128x128x128.block10 untraced (a checkout's first
# run: the float64 reference child runs beside the build and fills the
# cache; the assembly and XLA caches fill too), traced once, and up to
# [warm runs] more untraced runs while fewer than [budget] seconds have
# passed; each run takes a seed of its own. The start of each result line
# goes to stdout, whole logs to chiprun_out/rb3d/.
change=$(cd $1 && pwd)
parent=$(cd $2 && pwd)
warm=${3:-4}
seed=${4:-2147483040}
budget=${5:-600}
root=$PWD
out=$root/chiprun_out/rb3d
mkdir -p $out
start=$(date +%s)
cell=rb3d128x128x128.block10
one() {   # one <parent|change> <seed> <trace 0|1>
  log=$out/$cell.$1.trace$3.seed$2
  if [ $1 = parent ]; then cd $parent; else cd $change; fi
  t0=$(date +%s)
  python3 -m chipbench.run --workload $cell --seed $2 --seconds 10 --trace $3 > $log.log 2> $log.err
  echo "$cell $1 trace=$3 seed=$2 rc=$? wall=$(( $(date +%s) - t0 ))s $(tail -n 1 $log.log | cut -c1-2500)"
  cd $root
}
one parent $seed 0
tail -n 2 $out/$cell.parent.trace0.seed$seed.err
one change $seed 0
grep -h "chipbench \(built\|reference\|checks\)" $out/$cell.change.trace0.seed$seed.log | cut -c1-3000
seed=$((seed + 1))
one change $seed 1
grep -h "chipbench \(checks\|trace\)" $out/$cell.change.trace1.seed$seed.log | cut -c1-4000
i=0
while [ $i -lt $warm ] && [ $(( $(date +%s) - start )) -lt $budget ]; do
  seed=$((seed + 1)); i=$((i + 1))
  one change $seed 0
done
echo "rb3d done after $(( $(date +%s) - start ))s"
