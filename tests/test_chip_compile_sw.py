"""
Ask the chip's compiler about sw_ell255's pencil layer (chipbench cell
sw_ell255.block): what `[linear algebra] BANDED_MIN_Q = auto` and
`[fusion] FUSED_SOLVE = auto` resolve to for 256 pencil groups of 1,536
unknowns on a v5e, what the factorization then keeps resident, and that
the factor program and a stage solve fit the chip. Only the pencil's
STRUCTURE is assembled, from the 8 azimuthal orders of Nphi = 16 at the
published Ntheta = 256 (S = 1,536 already); G = 256 is a shape, as in
tests/test_chip_compile.py's `north_star`. The whole step cannot be grown
that way (its SWSH stacks are lifted constants of 8 groups): its scopes
and products are tests/test_config_sw_ell255.py's, its time the chip's.

A file of its own because the driver hands a file to one worker
(`--dist loadfile`); the fixtures and the rules they follow are
tests/test_chip_compile.py's (tests/test_chip_compile_cells.py says what
a second describer of the topology needs from the driver's command).
"""

import pathlib
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(pathlib.Path(__file__).parent))
from test_chip_compile import (  # noqa: E402,F401
    V5E_HBM_BYTES, _band_product_reads_the_store_in_place, _compile_f32,
    topo)

ROOT = pathlib.Path(__file__).resolve().parents[1]
G, S = 256, 1536
# the structural block size of the shallow-water pencil: on the chip both
# it and the re-blocked q = 224 read right in float32, and q = 7 ran 8.5
# times faster (135.19 against 15.88 steps/s, v5e, PERF.md, PR 33)
Q, NB = 7, 220


@pytest.fixture(scope="module")
def pencils(topo):
    from chipbench.manifest import load_module
    from dedalus_tpu.libraries import pencilops
    from dedalus_tpu.tools.config import config
    sw = load_module(ROOT / "chipbench" / "configs" / "sw_ell255.py")
    mp = pytest.MonkeyPatch()
    # FUSED_SOLVE = auto asks the device for its memory: a v5e's answer
    mp.setattr(pencilops, "device_memory_bytes", lambda: V5E_HBM_BYTES)
    try:
        # 8 groups would be dense by `auto`; 256 are 2.4 GB a stack, over
        # [linear algebra] BANDED_CUTOFF_BYTES: banded, as the cell expects
        cutoff = float(config["linear algebra"]["BANDED_CUTOFF_BYTES"])
        assert 8 * S * S * 4 < cutoff < G * S * S * 4
        dep = sw.build(0, size={"Nphi": 16, "matsolver": "banded"})
        solver = dep.solver
        ops, ts = solver.ops, solver.timestepper
        assert solver.pencil_shape == (8, S)
        one_chip = SingleDeviceSharding(topo.devices[0])
        sds = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731,E501
            tuple(shape), dtype, sharding=one_chip)
        grow = lambda a: None if a is None else sds(           # noqa: E731
            (G,) + a.shape[1:], a.dtype)
        M, L = (pencilops.BandedMatrix(grow(A.bands), grow(A.Vt), A.dsel)
                for A in (solver.M_mat, solver.L_mat))
        X, scalar = sds((G, S)), sds(())
        aux = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                           jax.eval_shape(ts._factor, M, L, scalar))
        yield {
            "ops": ops, "M": M, "L": L, "aux": aux[0],
            "incremental": ops.use_incremental_factor(G, 4),
            "factor": (ts._factor_uniq, (M, L, scalar)),
            "M@X": (ts._mx0, (M, X)),
            "L@X": (jax.jit(ops.matvec), (L, X)),
            "pair": (jax.jit(ops.matvec_pair), (M, L, X)),
            "stage_solve": (ts._stage_solve, (2, X, [X, X], [X, X], scalar,
                                              aux[0], M, L)),
        }
    finally:
        mp.undo()


def test_auto_keeps_the_structural_q_on_a_tpu(pencils):
    """What the defaults resolve to for 256 groups of 1,536 on a v5e: q
    stays the structural 7 (220 block rows, bands f32[256,11,1540] and 5
    pinned rows of L), one dispatch factors all groups, and FUSED_SOLVE =
    auto keeps the precomposed operators: 7 q^2 numbers a block row."""
    ops, aux = pencils["ops"], pencils["aux"]
    assert (ops.q, ops.NB, ops.n_pad) == (Q, NB, NB * Q)
    assert not pencils["incremental"]
    M, L = pencils["M"], pencils["L"]
    assert M.bands.shape == L.bands.shape == (G, 11, NB * Q)
    assert M.Vt is None and L.Vt.shape == (G, 5, NB * Q)
    assert "fsub" in aux and "interior" not in aux
    held = sum(np.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree.leaves(aux))
    # FwdOp 4 q^2 and BwdOp 3 q^2 per block row but the last, its q^2
    # inverse: 77 MB; V^T, Y^T and the capacitance's inverse: 16 MB; a, b
    operators = G * ((NB - 1) * 7 * Q * Q + Q * Q) * 4
    woodbury = G * (2 * 5 * NB * Q + 5 * 5) * 4
    assert held == operators + woodbury + 2 * 4 == 92_765_192


@pytest.mark.parametrize("program", ["factor", "stage_solve"])
def test_pencil_program_fits_a_v5e(pencils, program):
    compiled, _ = _compile_f32(*pencils[program])
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    # beside the 1.0 GB of SWSH stacks and the step's own temporaries
    assert held < 0.25 * V5E_HBM_BYTES


@pytest.mark.parametrize("program", ["M@X", "L@X", "pair"])
def test_band_product_is_one_tile(pencils, program):
    """A store of 17 MB (f32[256,11,1540]) fits one tile of the band
    product (tests/test_chip_compile.py, PR 38): the body once, with no
    `while` around it, the store read in place by the fusion that sums
    the diagonals, and nothing of a store's size among the temporaries."""
    compiled, text = _compile_f32(*pencils[program])
    assert pencils["ops"]._band_tiling == (NB * Q, 1)
    _band_product_reads_the_store_in_place(text, (G, 11, NB * Q),
                                           tiled=False)
    assert compiled.memory_analysis().temp_size_in_bytes < 8e6
