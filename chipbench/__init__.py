"""
chipbench: the chip benchmark of dedalus_tpu (BENCHMARK.json at the root).

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell once, in one process that holds the chip, and ends with one
JSON line. Everything that belongs to one configuration, one traffic mix,
one cell or one per-layer metric is a file of its own under configs/,
traffic/, workloads/ and layers/, found by the name BENCHMARK.json gives it;
adding one needs no edit to a file that is here. PERF.md says why each
exists. The old CPU wall-clock studies live in benchmarks/ and are not part
of this.
"""
