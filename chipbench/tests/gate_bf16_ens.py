"""
gate_bf16_cell.py for rb256x64-ens with ONLY the fleet's pencil products in
one bf16 pass (`matsolvers.FLEET_PRECISION`, the TPU's own default
precision), every other matmul of the program at 'highest' as
dedalus_tpu/__init__.py sets it: shows that the cell's limits bite on what
the fleet product does. The last line must say `correct: false`. No option
of the program or of run.py exists for this; the switch is here, in a
script nobody benchmarks with.

    python3 chipbench/tests/gate_bf16_ens.py [seed]     (on a TPU host)
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from chipbench import reference, run                     # noqa: E402
from chipbench.manifest import Manifest                  # noqa: E402

CELL = "rb256x64-ens.block10"


def main(argv):
    seed = int(argv[1]) if len(argv) > 1 else 1
    manifest = Manifest()
    cell = manifest.cell(CELL)
    spec = manifest.config_module(cell).SPEC
    # the reference child has to start before this process imports JAX
    reference.start(manifest.here / "configs" / f"{cell['config']}.py",
                    cell["config"], seed,
                    seeded=spec.get("seeded", True)).load()
    import jax
    from dedalus_tpu.libraries import matsolvers
    matsolvers.FLEET_PRECISION = jax.lax.Precision.DEFAULT
    return run.main(["--workload", CELL, "--seed", str(seed),
                     "--seconds", "5", "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main(sys.argv))
