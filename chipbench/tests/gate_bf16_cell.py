"""
gate_bf16.py for any cell: run it on the chip with every f32 matmul in ONE
bf16 pass, the TPU's own default that dedalus_tpu/__init__.py overrides.
The last line must say `correct: false`. No option of the program or of
run.py exists for this; the wrap is here, in a script nobody benchmarks
with.

    chiprun -- python3 chipbench/tests/gate_bf16_cell.py rb2048x1024.block10 [seed]
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from chipbench import reference, run                     # noqa: E402
from chipbench.manifest import Manifest                  # noqa: E402


def main(argv):
    name = argv[1]
    seed = int(argv[2]) if len(argv) > 2 else 1
    manifest = Manifest()
    cell = manifest.cell(name)
    spec = manifest.config_module(cell).SPEC
    # the reference child has to start before this process imports JAX
    reference.start(manifest.here / "configs" / f"{cell['config']}.py",
                    cell["config"], seed,
                    seeded=spec.get("seeded", True)).load()
    import jax
    with jax.default_matmul_precision("bfloat16"):
        return run.main(["--workload", name, "--seed", str(seed),
                         "--seconds", "5", "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main(sys.argv))
