#!/bin/sh
# Everything PERF.md's PR 26 entry reports, in one chip call: per cell one
# traced run of the parent's tree and one of this tree, three untraced runs
# of each, interleaved (parent, change, change, parent, parent, change; the
# two sides of a pair share a seed), then span_cost.py. The parent is an
# unpacked `git archive` with this tree's benchmark files laid over it, as
# the driver lays them, in a directory .gitignore lists:
#   mkdir -p .scratch/parent && git archive <parent> | tar -x -C .scratch/parent
#   cp BENCHMARK.json .scratch/parent/ && cp -r chipbench/. .scratch/parent/chipbench/
#   chiprun --timeout 3000 -- sh chipbench/tests/chip_spans.sh .scratch/parent
# The start of each result line goes to stdout, whole logs to
# chiprun_out/spans/ (no xplane is kept: six of them pass the 64 MiB that
# come back from a call).
parent=$1
root=$PWD
out=$root/chiprun_out/spans
mkdir -p $out
one() {   # one <parent|change> <cell> <seed> <trace 0|1>
  log=$out/$2.$1.trace$4.seed$3
  if [ $1 = parent ]; then cd $parent; else cd $root; fi
  python3 -m chipbench.run --workload $2 --seed $3 --seconds 10 --trace $4 > $log.log 2> $log.err
  echo "$2 $1 trace=$4 seed=$3 rc=$? $(tail -n 1 $log.log | cut -c1-1300)"
  cd $root
}
seed=2147481000
for cell in rb256x64.cfl rb256x64.block shear512.block; do
  one parent $cell $seed 1
  one change $cell $seed 1
  for side in "parent change" "change parent" "parent change"; do
    seed=$((seed + 1))
    for tree in $side; do one $tree $cell $seed 0; done
  done
  seed=$((seed + 1))
done
python3 chipbench/tests/span_cost.py $seed > $out/span_cost.log 2> $out/span_cost.err
echo "span_cost rc=$? $(tail -n 1 $out/span_cost.log | cut -c1-600)"
echo "spans done at $(date +%s)"
