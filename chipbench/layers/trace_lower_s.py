"""step program: JAX's own Python in the first calls, which no cache saves.
Sum of `discover_sec + trace_sec + lower_sec` over every row of the set-up
ledger: the abstract pass that finds a program's lifted constants, the
tracing of its body to a jaxpr, the lowering of that to an MLIR module.
Host clock (`jax.monitoring`'s time spans, nested ones counted once)."""

from chipbench import setupledger


def read(ctx):
    return setupledger.total_seconds("discover_sec", "trace_sec",
                                     "lower_sec")
