"""
User-facing API: `import dedalus_tpu.public as d3`
(reference: dedalus/public.py:4-14).
"""

from .core.coords import (Coordinate, CartesianCoordinates, DirectProduct,
                          PolarCoordinates, S2Coordinates,
                          SphericalCoordinates)
from .core.distributor import Distributor
from .core.domain import Domain
from .core.basis import (Jacobi, ChebyshevT, ChebyshevU, ChebyshevV, Legendre,
                         Ultraspherical, RealFourier, ComplexFourier, Fourier)
from .core.polar import DiskBasis, AnnulusBasis
from .core.sphere import SphereBasis, MulCosine
from .core.spherical3d import ShellBasis, BallBasis
from .core.field import Field, LockedField
from .core.problems import IVP, LBVP, NLBVP, EVP
from .core.operators import (
    AdvectiveCFL,
    Differentiate, Convert, Interpolate, Integrate, Average,
    AzimuthalAverageFactory as AzimuthalAverage,
    LiftFactory as Lift, LiftTau,
    Gradient, Divergence, Laplacian, Curl, Trace, TransposeComponents,
    SkewFactory as Skew, Radial, Azimuthal, Angular, SphericalEllProduct,
    TimeDerivative, UnaryGridFunction, GeneralFunction, GridWrapper as Grid,
    CoeffWrapper as Coeff, dt)
from .core.arithmetic import Add, Multiply, DotProduct, CrossProduct, Power
from .core.timesteppers import (schemes, add_scheme, MultistepIMEX,
                                RungeKuttaIMEX, CNAB1, SBDF1, CNAB2, MCNAB2,
                                SBDF2, CNLF2, SBDF3, SBDF4, RK111, RK222,
                                RK443, RKSMR, RKGFY)
from .core.solvers import (InitialValueSolver, LinearBoundaryValueSolver,
                           NonlinearBoundaryValueSolver, EigenvalueSolver)
from .core.ensemble import EnsembleSolver
from .core.evaluator import Evaluator
from .extras.flow_tools import CFL, GlobalFlowProperty, GlobalArrayReducer
from .tools.exceptions import (CheckpointError, SilentCorruptionError,
                               SolverHealthError)
from .tools.health import HealthMonitor

# lowercase operator aliases (reference: core/operators.py aliases)
cross = CrossProduct
dot = DotProduct
trans = TransposeComponents

# long-form aliases (reference exports both spellings)
InitialValueProblem = IVP
LinearBoundaryValueProblem = LBVP
NonlinearBoundaryValueProblem = NLBVP
EigenvalueProblem = EVP
Chebyshev = ChebyshevT
Component = Radial  # reference Component(operand, index) defaults radial
RadialComponent = Radial
AzimuthalComponent = Azimuthal
AngularComponent = Angular


def VectorField(dist, *args, **kw):
    """Module-level field factories (reference: core/field.py exports);
    equivalent to the Distributor methods."""
    return dist.VectorField(*args, **kw)


def TensorField(dist, *args, **kw):
    return dist.TensorField(*args, **kw)


def ScalarField(dist, *args, **kw):
    return dist.Field(*args, **kw)


from .tools.post import load_tasks_to_xarray
grad = Gradient
div = Divergence
lap = Laplacian
curl = Curl
trace = Trace
transpose = TransposeComponents
skew = Skew
integ = Integrate
ave = Average
lift = Lift
interp = Interpolate
radial = Radial
azimuthal = Azimuthal
angular = Angular
# reference-parity aliases (reference: core/operators.py:1028 interpolate,
# :1449 convert; Transpose as the TransposeComponents shorthand)
Transpose = TransposeComponents
convert = Convert


def interpolate(arg, **positions):
    """Iterated interpolation: interpolate(f, x=0.5, z=1.0) (reference:
    core/operators.py:1028)."""
    for coord, position in positions.items():
        arg = Interpolate(arg, coord, position)
    return arg



# Warm-pool solver service (dedalus_tpu/service/; docs/serving.md): the
# lightweight blocking client for a `python -m dedalus_tpu serve` daemon.
# Imported last; the client touches none of the solver stack — the
# daemon owns all solver state and compilation.
from .service.client import ServiceClient
from .service.protocol import ServiceError, SpecError
