#!/bin/sh
# The chip calls of PR 37 (the set-up ledger); <phases> is one phase or several, comma-separated:
#   mkdir -p .scratch/archive && git archive $(git write-tree) | tar -x -C .scratch/archive
#   mkdir -p .scratch/parent && git archive <parent> | tar -x -C .scratch/parent
#   cp BENCHMARK.json .scratch/parent/ && cp -r chipbench/. .scratch/parent/chipbench/
#   chiprun --timeout 3500 -- sh chipbench/tests/chip_setup.sh <phases> .scratch/archive .scratch/parent [first seed]
# The XLA cache is the one the machine comes with (JAX_COMPILATION_CACHE_DIR as
# the call finds it: the driver's runs read the same one), shared by both trees:
# no HLO differs between them, so what the parent's run leaves the change's finds
# (`xla_cache_misses` 0). The `cold-` phases set it to an empty temporary
# directory instead: a checkout's first run, every program a `miss`.
# Phases:
#   small    rb256x64.cfl and rb256x64.block: the parent once (untraced), then
#            the change traced, then five untraced pairs of each, parent against
#            change, the two sides of a pair on one seed in alternating order
#   shear | sw | rb2048 | f64
#            that cell on the change, traced, once
#   cold-shear | cold-sw | cold-rb2048 | cold-f64
#            that cell on the change with an empty XLA cache: untraced (the
#            compiles, by program), then traced (warm from it)
#   f64-lowering
#            rb256x64-f64.block10 untraced three times, each with the main
#            thread's Python stack every 5 s beside its log (<log>.ledger.json.stacks)
#   f64-pair rb256x64-f64.block10 untraced, the parent then the change on one
#            seed: whether both pay the same set-up on one machine
#   cost     chipbench/tests/ledger_cost.py: the bracket's own microseconds
# Each run goes through chipbench/tests/setup_rows.py (run.py's own main, then
# the whole ledger into <log>.ledger.json) and prints the start of its result
# line and its `chipbench checks` line (setup_stages, build_phases with the
# twelve largest program rows); whole logs go to chiprun_out/setup/.
change=$(cd $2 && pwd)
parent=$(cd $3 && pwd)
seed=${4:-2147470137}   # every run (every pair) takes the next one
root=$PWD
out=$root/chiprun_out/setup
mkdir -p $out
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}"
one() {   # one <parent|change> <cell> <seed> <trace 0|1> [tag]
  log=$out/$2.$1.trace$4.seed$3${5:+.$5}
  if [ $1 = parent ]; then cd $parent; else cd $change; fi
  t0=$(date +%s)
  timeout 1700 python3 chipbench/tests/setup_rows.py $log.ledger.json ${STACKS:+--stacks-every $STACKS} --workload $2 --seed $3 --seconds 10 --trace $4 > $log.log 2> $log.err
  echo "$2 $1 trace=$4 seed=$3 ${5:-} rc=$? wall=$(( $(date +%s) - t0 ))s $(tail -n 1 $log.log | cut -c1-2600)"
  grep -h "chipbench checks" $log.log | cut -c1-9000
  cd $root
}
traced() { one change $1 $seed 1 warm; seed=$((seed + 1)); }
cold_then_traced() {   # on an empty XLA cache of its own
  was=$JAX_COMPILATION_CACHE_DIR
  export JAX_COMPILATION_CACHE_DIR=$(mktemp -d)
  one change $1 $seed 0 cold; seed=$((seed + 1))
  one change $1 $seed 1 warmed; seed=$((seed + 1))
  rm -rf $JAX_COMPILATION_CACHE_DIR
  if [ -n "$was" ]; then export JAX_COMPILATION_CACHE_DIR=$was; else unset JAX_COMPILATION_CACHE_DIR; fi
}
cell_of() {
  case $1 in
  shear) echo shear512.block ;; sw) echo sw_ell255.block ;;
  rb2048) echo rb2048x1024.block10 ;; f64) echo rb256x64-f64.block10 ;;
  esac
}
for phase in $(echo $1 | tr , ' '); do
  case $phase in
  small)
    for cell in rb256x64.cfl rb256x64.block; do
      one parent $cell $seed 0 first; seed=$((seed + 1))
      one change $cell $seed 1 warm; seed=$((seed + 1))
    done
    for cell in rb256x64.cfl rb256x64.block; do
      for side in "parent change" "change parent" "parent change" "change parent" "parent change"; do
        for tree in $side; do one $tree $cell $seed 0 pair; done
        seed=$((seed + 1))
      done
    done ;;
  shear|sw|rb2048|f64) traced $(cell_of $phase) ;;
  cold-shear|cold-sw|cold-rb2048|cold-f64) cold_then_traced $(cell_of ${phase#cold-}) ;;
  f64-lowering)
    # how often the scan program's lowering is the slow one (1 s or 50), and
    # where it is then: three untraced runs, the main thread's stack every 5 s
    STACKS=5
    for i in 1 2 3; do one change rb256x64-f64.block10 $seed 0 stacks; seed=$((seed + 1)); done
    STACKS= ;;
  f64-pair)
    one parent rb256x64-f64.block10 $seed 0 pair
    one change rb256x64-f64.block10 $seed 0 pair; seed=$((seed + 1)) ;;
  cost)
    python3 chipbench/tests/ledger_cost.py > $out/ledger_cost.log 2> $out/ledger_cost.err
    echo "ledger_cost rc=$? $(tail -n 1 $out/ledger_cost.log)" ;;
  *) echo "unknown phase $phase" ;;
  esac
done
echo "setup done at $(date +%s)"
