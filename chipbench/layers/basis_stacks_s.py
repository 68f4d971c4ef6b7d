"""transforms: the curvilinear bases' host-built matrix stacks (SWSH by
quadrature, m by m): the build phase `basis_stacks`, summed over every
solver the process built and the process-level phases (a stack is built
the first time something needs it: a field transformed before any solver
exists, the LBVP's build, the deployment's solver's). Host clock."""

from chipbench import setupledger


def read(ctx):
    return setupledger.process_phase_seconds("basis_stacks")
