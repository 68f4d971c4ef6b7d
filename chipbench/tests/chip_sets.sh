#!/bin/sh
# Runs of one cell on the chip, each a fresh process with another seed, as
# the driver makes them. Run 0 compiles and belongs to no set; `traced` is
# the --trace 1 run, its raw trace kept; runs 1..12 are two sets of 6.
#   sh chipbench/tests/chip_sets.sh <cell> <seconds> <first_seed> <run>...
# where <run> is 0, traced, or a number. Result lines go to stdout, whole
# logs to $CHIPBENCH_OUT/sets/<cell>/ (chiprun_out/ unless set).
cell=$1; seconds=$2; seed=$3; shift 3
root=${CHIPBENCH_OUT:-chiprun_out}
out=$root/sets/$cell
mkdir -p $out
for run in "$@"; do
  if [ "$run" = traced ]; then
    python3 -m chipbench.run --workload $cell --seed $((seed + 50)) --seconds $seconds --trace 1 --keep-trace $root/traces > $out/traced.log 2> $out/traced.err
    echo "$cell traced rc=$? $(tail -n 1 $out/traced.log | cut -c1-1500)"
  else
    python3 -m chipbench.run --workload $cell --seed $((seed + run)) --seconds $seconds --trace 0 > $out/run$run.log 2> $out/run$run.err
    echo "$cell run$run rc=$? $(tail -n 1 $out/run$run.log | cut -c1-400)"
  fi
done
