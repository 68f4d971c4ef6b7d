"""entry: the measurement's own gap: `unnamed_sec` of the deployment's
solver, the wall of its whole `__init__` less every build phase and every
program's first call booked inside it. Host clock."""

from chipbench import setupledger


def read(ctx):
    return setupledger.phase_seconds(ctx, "unnamed_sec")
