"""host assembly: host -> device copies that are not M's and L's (those
are `factor_s`'s): the build phase `upload` of the deployment's solver:
the lifted constants of its programs (stacks, planes, masks) on their
first use. Host clock around the copies' launch."""

from chipbench import setupledger


def read(ctx):
    return setupledger.phase_seconds(ctx, "upload_sec")
