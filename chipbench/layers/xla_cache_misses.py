"""step program: programs the backend compiled in this run and wrote to the
persistent cache: rows of the set-up ledger with `cache: miss`
(`/jax/compilation_cache/cache_misses`). 0 on a warm cache; what a
checkout's first run pays."""

from chipbench import setupledger


def read(ctx):
    return setupledger.total_seconds("cache_misses")
