"""pencil solve: the float64 route's host work at build: the float64
copies of M and L, their slicing into int8 planes and float32 pairs, the
double-double state (`DDIVPRunner.__init__`): the build phase
`dd_prepare`. Host clock."""

from chipbench import setupledger


def read(ctx):
    return setupledger.phase_seconds(ctx, "dd_prepare_sec")
