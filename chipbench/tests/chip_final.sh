#!/bin/sh
# Proof that the committed files are enough, and the measurement of the
# cells as they are committed: everything runs from an unpacked
# `git archive $(git write-tree)` in a directory .gitignore lists.
#   mkdir -p .scratch/archive && git archive $(git write-tree) | tar -x -C .scratch/archive
#   chiprun -- sh chipbench/tests/chip_final.sh .scratch/archive
# Run 0 of a cell is the first in that checkout (no reference, no assembly
# cache); the later ones find everything: compare their setup_s.
export CHIPBENCH_OUT=$PWD/chiprun_out/final
cd "$1" || exit 1
sets="sh chipbench/tests/chip_sets.sh"
$sets rb256x64.cfl 30 400 0 traced 1 2 3 4 5 6 7 8 9 10 11 12
$sets rb256x64.block 10 500 0 traced 1 2 3 4 5 6
$sets shear512.block 10 600 0 traced
