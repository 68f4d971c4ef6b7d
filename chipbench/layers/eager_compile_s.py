"""entry: programs JAX made for operations dispatched one by one from the
host (initial conditions, field arithmetic, a state scattered op by op):
the seconds of the set-up ledger's eager aggregate, i.e. traces, lowerings
and compiles that arrived with no program row open. Host clock."""

from chipbench import setupledger


def read(ctx):
    found = setupledger.totals()
    return None if found is None else float(found["eager"]["sec"])
