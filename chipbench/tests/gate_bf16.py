"""
Show that the gate bites: run rb256x64.block on the chip with every f32
matmul in ONE bf16 pass, the TPU's own default that dedalus_tpu/__init__.py
overrides (PR 22, run 1: 786 steps/s, wall errors 4e-3, and wrong). The
last line must say `correct: false`. No option of the program or of run.py
exists for this; the wrap is here, in a script nobody benchmarks with.

    chiprun -- python3 chipbench/tests/gate_bf16.py
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from chipbench import reference, run                     # noqa: E402
from chipbench.manifest import Manifest                  # noqa: E402

SEED = 1


def main():
    manifest = Manifest()
    cell = manifest.cell("rb256x64.block")
    # the reference child has to start before this process imports JAX
    reference.start(manifest.here / "configs" / f"{cell['config']}.py",
                    cell["config"], SEED).load()
    import jax
    with jax.default_matmul_precision("bfloat16"):
        return run.main(["--workload", cell["name"], "--seed", str(SEED),
                         "--seconds", "5", "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
