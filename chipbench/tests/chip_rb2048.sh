#!/bin/sh
# Everything PERF.md's PR 28 entry reports of the new cell, in one chip
# call, from the committed files alone:
#   mkdir -p .scratch/archive && git archive $(git write-tree) | tar -x -C .scratch/archive
#   mkdir -p .scratch/parent && git archive <parent> | tar -x -C .scratch/parent
#   cp BENCHMARK.json .scratch/parent/ && cp -r chipbench/. .scratch/parent/chipbench/
#   chiprun --timeout 3500 -- sh chipbench/tests/chip_rb2048.sh .scratch/archive .scratch/parent [warm runs] [controls yes|no] [first seed]
# rb2048x1024.block10 untraced (the first run of a checkout, which computes
# the float64 reference and fills the assembly and XLA caches, then warm
# runs, each with a seed of its own), traced once, and the gate run with
# one bf16 pass, which must read `correct: false`; then the three old
# cells, parent against change (the two sides of a pair share a seed). The
# parent cannot run the new cell: its one run there shows the manifest's
# message and exit code 1. The start of each result line goes to stdout,
# whole logs to chiprun_out/rb2048/.
change=$(cd $1 && pwd)
parent=$(cd $2 && pwd)
warm=${3:-2}
controls=${4:-yes}      # "no": leave the three old cells out
seed=${5:-2147482048}   # every run takes the next one: a call of its own, a base of its own
root=$PWD
out=$root/chiprun_out/rb2048
mkdir -p $out
one() {   # one <parent|change> <cell> <seed> <trace 0|1>
  log=$out/$2.$1.trace$4.seed$3
  if [ $1 = parent ]; then cd $parent; else cd $change; fi
  t0=$(date +%s)
  python3 -m chipbench.run --workload $2 --seed $3 --seconds 10 --trace $4 > $log.log 2> $log.err
  echo "$2 $1 trace=$4 seed=$3 rc=$? wall=$(( $(date +%s) - t0 ))s $(tail -n 1 $log.log | cut -c1-1500)"
  cd $root
}
cell=rb2048x1024.block10
one parent $cell $seed 0
tail -n 2 $out/$cell.parent.trace0.seed$seed.err
one change $cell $seed 0
i=0
while [ $i -lt $warm ]; do
  seed=$((seed + 1)); i=$((i + 1))
  one change $cell $seed 0
done
seed=$((seed + 1))
one change $cell $seed 1
grep -h "chipbench \(built\|reference\|checks\|trace\)" $out/$cell.change.trace1.seed$seed.log | cut -c1-3000
seed=$((seed + 1))
cd $change
python3 chipbench/tests/gate_bf16_cell.py $cell $seed > $out/gate_bf16.log 2> $out/gate_bf16.err
echo "gate_bf16 rc=$? $(tail -n 1 $out/gate_bf16.log | cut -c1-600)"
grep -h "chipbench \(reference\|checks\)" $out/gate_bf16.log | cut -c1-1200
cd $root
[ $controls = no ] && { echo "rb2048 done at $(date +%s)"; exit 0; }
for cell in rb256x64.block rb256x64.cfl shear512.block; do
  for side in "parent change" "change parent"; do
    seed=$((seed + 1))
    for tree in $side; do one $tree $cell $seed 0; done
  done
done
echo "rb2048 done at $(date +%s)"
