"""step program: reading and deserialising executables from the persistent
XLA cache. Sum of `retrieval_sec` over every row of the set-up ledger
(`/jax/compilation_cache/cache_retrieval_time_sec`, a part of the row's
`backend_sec`). Host clock."""

from chipbench import setupledger


def read(ctx):
    return setupledger.total_seconds("retrieval_sec")
