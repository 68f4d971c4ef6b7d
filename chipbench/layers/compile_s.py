"""step program: what tracing, lowering and compiling or loading programs
cost the host between import and the window. Sum of `first_call_sec` over
every row of the program's set-up ledger (`tools/retrace.py`: the host
wall of each program's first call: discovery pass and first launch; the
launch is asynchronous, so no device time is in it). Host clock around
host work."""

from chipbench import setupledger


def read(ctx):
    return setupledger.total_seconds("first_call_sec")
