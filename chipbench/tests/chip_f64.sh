#!/bin/sh
# The chip calls of PR 35 (rb256x64-f64.block10); <phases> is one phase or several, comma-separated:
#   mkdir -p .scratch/archive && git archive $(git write-tree) | tar -x -C .scratch/archive
#   mkdir -p .scratch/parent && git archive <parent> | tar -x -C .scratch/parent
#   cp BENCHMARK.json .scratch/parent/ && cp -r chipbench/. .scratch/parent/chipbench/
#   chiprun --timeout 3500 -- sh chipbench/tests/chip_f64.sh <phases> .scratch/archive .scratch/parent [first seed]
# Phases:
#   first    the cell on the change, untraced (a fresh checkout's set-up: the
#            reference child and three large compiles) then traced, with every
#            device op of the traced block listed
#   parent   the parent with this PR's benchmark files laid over it, once: it
#            has to end by itself
#   cell     the cell from the committed files: six untraced runs, each a seed
#            of its own, and one traced
#   more     six more untraced runs of the cell, each a seed of its own (how
#            often a block stalls: PERF.md section 2)
#   never    the other float64 route, once: [execution] EMULATED_F64 = never
#            (a dedalus_tpu.cfg written into the checkout for the run and
#            removed), XLA's software float64 through DenseOps
#   digits   chipbench/tests/f64_digits.py: the ten reference steps with 3 and
#            1 refinement sweeps in place of the runner's 2 (where the dd
#            route's digits go; after a phase that left the reference cached)
#   ops      chipbench/tests/f64_ops.py: the pieces of one dd step, each against
#            NumPy long double (which operation loses the digits on the chip)
#   steps    chipbench/tests/f64_steps.py: the error after each of the ten
#            reference steps, variable by variable, against the CPU's native
#            float64 states in .scratch/ref_states.npz
#   cfl      three more pairs of rb256x64.cfl, parent against change
#   oldtrace one old cell traced on the parent under this PR's benchmark files,
#            and on the change (the overlay must leave old cells as they were)
#   controls old cells whose host loop this PR touches, parent against change,
#            pairs sharing a seed in alternating order
# The start of each result line goes to stdout, whole logs to chiprun_out/f64/.
change=$(cd $2 && pwd)
parent=$(cd $3 && pwd)
seed=${4:-2147480035}   # every run takes the next one
root=$PWD
out=$root/chiprun_out/f64
mkdir -p $out
cell=rb256x64-f64.block10
one() {   # one <parent|change> <cell> <seed> <trace 0|1> [EMULATED_F64] [more arguments]
  log=$out/$2.$1.trace$4.seed$3${5:+.$5}
  if [ $1 = parent ]; then cd $parent; else cd $change; fi
  [ -n "$5" ] && printf '[execution]\nEMULATED_F64 = %s\n' $5 > dedalus_tpu.cfg
  t0=$(date +%s)
  timeout 1500 python3 -m chipbench.run --workload $2 --seed $3 --seconds 10 --trace $4 $6 $7 > $log.log 2> $log.err
  echo "$2 $1 trace=$4 seed=$3 f64=${5:-default} rc=$? wall=$(( $(date +%s) - t0 ))s $(tail -n 1 $log.log | cut -c1-2500)"
  grep -h "chipbench \(built\|reference\|checks\)" $log.log | cut -c1-2400
  [ $4 = 1 ] && grep -h "chipbench trace" $log.log | cut -c1-3000
  rm -f dedalus_tpu.cfg
  cd $root
}
ops() {   # every device op of a kept trace, then the trace goes
  python3 -c "
from chipbench import tracered
r = tracered.reduce(tracered.Trace.from_file('$out/trace/$cell.xplane.pb'))
print('device_ops', [[k, round(v, 6)] for k, v in r['device_ops'][:60]])
print('unscoped_s', r['unscoped_s'], 'busy_s', r['busy_s'], 'window_s', r['window_s'])
"
  rm -rf $out/trace
}
for phase in $(echo $1 | tr , ' '); do
case $phase in
first)
  one change $cell $seed 0; seed=$((seed + 1))
  tail -n 5 $out/$cell.change.trace0.seed$((seed - 1)).err | cut -c1-600
  one change $cell $seed 1 "" --keep-trace $out/trace; seed=$((seed + 1))
  tail -n 5 $out/$cell.change.trace1.seed$((seed - 1)).err | cut -c1-600
  ops ;;
parent)
  one parent $cell $seed 0
  tail -n 3 $out/$cell.parent.trace0.seed$seed.err
  seed=$((seed + 1)) ;;
cell)
  i=0
  while [ $i -lt 6 ]; do
    one change $cell $seed 0; seed=$((seed + 1)); i=$((i + 1))
  done
  one change $cell $seed 1 "" --keep-trace $out/trace; seed=$((seed + 1))
  ops ;;
more)
  i=0
  while [ $i -lt 6 ]; do
    one change $cell $seed 0; seed=$((seed + 1)); i=$((i + 1))
  done ;;
never)
  one change $cell $seed 0 never; seed=$((seed + 1))
  tail -n 5 $out/$cell.change.trace0.seed$((seed - 1)).never.err | cut -c1-600 ;;
digits)
  cd $change
  timeout 1500 python3 chipbench/tests/f64_digits.py 3 1 > $out/digits.log 2> $out/digits.err
  echo "digits rc=$? $(grep refine $out/digits.log | tr '\n' ' ')"
  cd $root ;;
ops)
  cd $change
  cp $root/chipbench/tests/f64_ops.py chipbench/tests/f64_ops.py
  timeout 1200 python3 chipbench/tests/f64_ops.py > $out/ops.log 2> $out/ops.err
  echo "ops rc=$?"; grep piece $out/ops.log; tail -n 3 $out/ops.err | cut -c1-400
  cd $root ;;
steps)
  cd $change
  cp $root/chipbench/tests/f64_steps.py chipbench/tests/f64_steps.py
  timeout 1200 python3 chipbench/tests/f64_steps.py $root/.scratch/ref_states.npz > $out/steps.log 2> $out/steps.err
  echo "steps rc=$?"; grep step $out/steps.log | cut -c1-700; tail -n 2 $out/steps.err | cut -c1-300
  cd $root ;;
cfl)
  for side in "parent change" "change parent" "parent change"; do
    seed=$((seed + 1))
    for tree in $side; do one $tree rb256x64.cfl $seed 0; done
  done ;;
oldtrace)
  one parent rb256x64.block $seed 1; one change rb256x64.block $seed 1
  seed=$((seed + 1)) ;;
controls)
  for old in rb256x64.block rb256x64.cfl; do
    for side in "parent change" "change parent"; do
      seed=$((seed + 1))
      for tree in $side; do one $tree $old $seed 0; done
    done
  done ;;
esac
echo "f64 $phase done at $(date +%s)"
done
