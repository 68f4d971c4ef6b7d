"""
What the step-loop spans cost when they are live and no profiler runs:
rb256x64.cfl, untraced, under `tracing.enable()` (every span of every
iteration recorded into the ring). Compare `steps_per_s` of the last line
with that of the same script run with `off` (the control: the same
process, the switch not thrown). No option of run.py exists for this; the
switch is thrown here, in a script nobody benchmarks with.

    chiprun -- python3 chipbench/tests/span_cost.py <seed> [off]
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from chipbench import reference, run                     # noqa: E402
from chipbench.manifest import Manifest                  # noqa: E402


def main(seed, switch="on"):
    manifest = Manifest()
    cell = manifest.cell("rb256x64.cfl")
    # the reference child has to start before this process imports JAX,
    # under the cache key run.main will look for
    seeded = manifest.config_module(cell).SPEC.get("seeded", True)
    reference.start(manifest.here / "configs" / f"{cell['config']}.py",
                    cell["config"], int(seed), seeded=seeded).load()
    from dedalus_tpu.tools import tracing
    if switch != "off":
        tracing.enable()
    return run.main(["--workload", cell["name"], "--seed", seed,
                     "--seconds", "10", "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
