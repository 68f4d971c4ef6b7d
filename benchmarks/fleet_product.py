"""The fleet's dense pencil product on the chip, outside every timed
window: one (G, S, S) stack shared by N members (RB 256x64's shape: 128
pencils of 526, a random stack made on the device) against N vectors, in
the two forms `matsolvers.fleet_contraction` chooses between — `vpu`, the
multiply-reduce vmapped over the members (what `vmap` of `batched_matvec`
gives), and `mxu`, `fleet_matvec`'s one contraction over S — at each N of
`--members`. Each form runs `--chain` products in ONE program (a scan,
x <- A x / |A|, as a fleet step runs its products: the stack's copy into
the dot's layout, where the compiler makes one, falls once a launch, at
its head), and the time is per product. One process, which holds the
chip, on a TPU host:

    python3 benchmarks/fleet_product.py

Here, `JAX_PLATFORMS=cpu python3 benchmarks/fleet_product.py --groups 8
--size 64 --members 4 32 --reps 2` rehearses it small. Prints one JSON line
per (N, form): milliseconds a product, the GB/s of the bytes it has to
read and write (the stack once, the vectors in and out) and the TFLOP/s of
its 2 N G S^2 operations, and the largest difference between the two
forms' results over the largest entry. A diagnosis, not a metric: the
cell's `fleet_product_ms_per_step` is the measurement."""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, default=128)
    ap.add_argument("--size", type=int, default=526)
    ap.add_argument("--members", type=int, nargs="*",
                    default=[8, 16, 32, 64, 128, 256, 512, 1024])
    ap.add_argument("--chain", type=int, default=10)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import dedalus_tpu  # noqa: F401  (matmul precision 'highest')
    from dedalus_tpu.libraries import matsolvers
    G, S, K = args.groups, args.size, args.chain
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (G, S, S), jnp.float32) / S ** 0.5
    forms = {
        "vpu": jax.vmap(matsolvers.batched_matvec, in_axes=(None, 0)),
        "mxu": matsolvers.fleet_matvec,
    }

    def chained(product):
        def run(A, X):
            def body(x, _):
                return product(A, x), None
            return jax.lax.scan(body, X, None, length=K)[0]
        return jax.jit(run)

    programs = {name: chained(fn) for name, fn in forms.items()}
    device = jax.devices()[0]
    for N in args.members:
        X = jax.random.normal(jax.random.PRNGKey(N), (N, G, S), jnp.float32)
        outs = {}
        for name, prog in programs.items():
            out = jax.block_until_ready(prog(A, X))     # compile + first
            t0 = time.perf_counter()
            for _ in range(args.reps):
                out = prog(A, X)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / args.reps / K * 1e3
            outs[name] = out
            nbytes = (G * S * S + 2 * N * G * S) * 4
            print(json.dumps({
                "device": device.device_kind, "G": G, "S": S, "N": N,
                "form": name, "ms_per_product": ms,
                "GB_per_s": nbytes / ms / 1e6,
                "TFLOP_per_s": 2 * N * G * S * S / ms / 1e9,
                "rule_takes": "mxu" if matsolvers.fleet_contraction(
                    N, jax.default_backend()) else "vpu"}), flush=True)
        diff = float(jnp.max(jnp.abs(outs["vpu"] - outs["mxu"]))
                     / jnp.max(jnp.abs(outs["vpu"])))
        print(json.dumps({"N": N, "max_rel_diff_vpu_mxu": diff}),
              flush=True)


if __name__ == "__main__":
    main()
