"""entry: the configuration's script outside any solver's construction
(fields, initial conditions, an LBVP's solve): the harness's `build_s`
less the `init_sec` of every solver the process built. Host clock."""

from chipbench import setupledger


def read(ctx):
    return setupledger.script_seconds(ctx)
