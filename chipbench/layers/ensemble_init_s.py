"""host assembly, ensemble: what a fleet costs its set-up beyond its
template's build — the fleet's state and operands, every member's initial
conditions (the configuration's script, once per member) and their upload:
the build phase `ensemble` of the template's ledger, which the fleet books
to. Host clock around host work; the uploads are asynchronous."""

from chipbench import setupledger


def read(ctx):
    return setupledger.phase_seconds(ctx, "ensemble_sec")
