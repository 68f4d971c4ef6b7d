#!/bin/sh
# Everything PERF.md's PR 23 entry reports, in one chip call: per cell the
# compiling run 0 and a traced run, the bf16 gate, then runs 1..12 three at
# a time round the cells, so that a call that has to stop early (the
# optional deadline, in epoch seconds) still has every cell's first runs.
deadline=${1:-0}
sets="sh chipbench/tests/chip_sets.sh"
time_left() { [ "$deadline" -eq 0 ] || [ $(( $(date +%s) + $1 )) -lt "$deadline" ]; }
$sets rb256x64.block 10 100 0 traced
python3 chipbench/tests/gate_bf16.py > chiprun_out/gate_bf16.log 2> chiprun_out/gate_bf16.err
echo "gate_bf16 rc=$? $(grep '^chipbench reference' chiprun_out/gate_bf16.log) $(tail -n 1 chiprun_out/gate_bf16.log | cut -c1-400)"
$sets rb256x64.cfl 50 200 0 traced
$sets shear512.block 10 300 0 traced
for round in "1 2 3" "4 5 6" "7 8 9" "10 11 12"; do
  time_left 150 && $sets rb256x64.block 10 100 $round
  time_left 300 && $sets rb256x64.cfl 50 200 $round
  time_left 200 && $sets shear512.block 10 300 $round
done
echo "measure done at $(date +%s)"
