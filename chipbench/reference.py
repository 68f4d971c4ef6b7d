"""
The reference trajectory: the same deployment from the same seed, a few
fixed-dt steps in float64 on the host CPU backend through the plainest path
the package has (unsharded, unfused, dense per-pencil solve, transforms as
dense matrix products instead of FFTs). It runs in a child process with
JAX_PLATFORMS=cpu, started BEFORE the parent imports JAX (a process that has
touched JAX holds the chip; the child never asks for it), and is kept as
.cache/chipbench/ref-<config>-<seed>.npz in the checkout, so only the first
run of a (configuration, seed) pays it — beside the parent's own build.

An independent NumPy pseudo-spectral reference with no package code at all
is an open question in PERF.md.
"""

import argparse
import os
import pathlib
import subprocess
import sys

from .manifest import ROOT, ManifestError

PLAIN_PATH = {  # config section -> key -> value, set in the child only
    "fusion": {"FUSED_TRANSFORMS": "off", "FUSED_SOLVE": "off",
               "FUSED_MATVEC": "off"},
    "transforms": {"DEFAULT_LIBRARY": "matrix"},
}


def cache_path(config_name, seed, seeded, rehearse):
    key = str(int(seed)) if seeded else "any"
    tail = ".cpu-rehearsal" if rehearse else ""
    return ROOT / ".cache" / "chipbench" / f"ref-{config_name}-{key}{tail}.npz"


class Pending:
    """A reference that is cached or on its way. The child is CPU-only
    (JAX_PLATFORMS=cpu) and never needs the chip, so it may run beside
    the parent's build and compile; `load` waits for it."""

    def __init__(self, path, child=None):
        self.path = path
        self.child = child
        self.computed = child is not None

    def load(self):
        import numpy as np
        if self.child is not None:
            output, _ = self.child.communicate()
            code, self.child = self.child.returncode, None
            if code != 0 or not self.path.is_file():
                sys.stderr.write(output[-4000:])
                raise ManifestError(f"reference child failed (exit {code})")
        return np.load(self.path)

    def abandon(self):
        if self.child is not None:
            self.child.kill()
            self.child.communicate()
            self.child = None


def start(config_file, config_name, seed, seeded=True, rehearse=False):
    """The cached reference, or a child computing it. Must be called
    before the caller imports JAX: a child started by a process that
    holds the chip is the thing to avoid."""
    path = cache_path(config_name, seed, seeded, rehearse)
    if path.is_file():
        return Pending(path)
    if "jax" in sys.modules:
        raise RuntimeError("the reference child must start before this "
                           "process imports JAX")
    path.parent.mkdir(parents=True, exist_ok=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "chipbench.reference",
           "--config-file", str(config_file), "--seed", str(int(seed)),
           "--out", str(path)] + (["--rehearse"] if rehearse else [])
    return Pending(path, subprocess.Popen(
        cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config-file", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("the reference runs with JAX_PLATFORMS=cpu")

    import numpy as np
    from dedalus_tpu.tools.config import config
    for section, keys in PLAIN_PATH.items():
        for key, value in keys.items():
            config[section][key] = value
    from .manifest import load_module
    module = load_module(args.config_file)
    spec = module.SPEC
    size = dict(spec["rehearsal"]) if args.rehearse else {}
    size["matsolver"] = "dense"
    ref = spec["reference"]
    dep = module.build(args.seed, dtype=np.dtype(ref["dtype"]), size=size)
    for _ in range(ref["steps"]):
        dep.solver.step(ref["dt"])
    out = pathlib.Path(args.out)
    tmp = out.with_name(out.name + f".tmp{os.getpid()}.npz")
    np.savez(tmp, coeffs=dep.compared(), steps=ref["steps"], dt=ref["dt"])
    os.replace(tmp, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
