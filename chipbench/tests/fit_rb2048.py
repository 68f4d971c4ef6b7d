"""
The fit of rb2048x1024, asked twice.

    JAX_PLATFORMS=cpu python3 chipbench/tests/fit_rb2048.py --describe
        no chip: builds the deployment on the CPU under the TPU backend's
        name (the branch the chip takes), then compiles its programs — the
        incremental factor's chunk program, M @ X0, stage_eval, stage_solve
        — for a DESCRIBED v5e from ShapeDtypeStructs and prints each
        program's memory_analysis(). A compile that passes is not a chip
        run. (tests/test_chip_compile.py keeps the factor-chunk and
        stage_solve compiles, from shapes alone, as tier-1 tests.)

    chiprun --timeout 1800 -- python3 chipbench/tests/fit_rb2048.py
        on the chip: build, factor, ten solver.step(dt), one
        step_many(10, dt), memory_stats() after each, finite.

`--size Nx=1024` runs the one cut the issue allows. Every option of
dedalus_tpu.cfg stays at its default. One JSON line per phase on stdout.
"""

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
T0 = time.time()


def say(phase, **facts):
    import resource
    print(json.dumps({"phase": phase, "t": round(time.time() - T0, 1),
                      "host_peak_rss_MB": resource.getrusage(
                          resource.RUSAGE_SELF).ru_maxrss // 1024,
                      **facts}), flush=True)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--describe", action="store_true")
    parser.add_argument("--size", action="append", default=[],
                        metavar="KEY=VALUE")
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="run the chip phases on the CPU (a rehearsal "
                             "of this script, at a --size the CPU holds)")
    args = parser.parse_args(argv)
    size = {k: (v if k == "matsolver" else int(v))
            for k, v in (kv.split("=") for kv in args.size)}
    os.environ.setdefault("DEDALUS_TPU_ASSEMBLY_CACHE",
                          str(ROOT / ".cache" / "assembly"))
    if args.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.manifest import load_module
    config = load_module(ROOT / "chipbench" / "configs" / "rb2048x1024.py")
    device = jax.devices()[0]
    say("device", platform=device.platform, kind=device.device_kind)
    if args.describe:
        if device.platform != "cpu":
            raise SystemExit("--describe needs JAX_PLATFORMS=cpu")
        jax.default_backend = lambda: "tpu"     # the branch the chip takes
    elif device.platform != "tpu" and not args.rehearse_cpu:
        raise SystemExit("no TPU: nothing is measured on anything else")

    def memory():
        stats = device.memory_stats() or {}
        return {k: round(stats.get(k, 0) / 1e6, 1)
                for k in ("bytes_in_use", "peak_bytes_in_use",
                          "bytes_limit")}

    dep = config.build(0, size=size or None)
    solver = dep.solver
    ops, ts = solver.ops, solver.timestepper
    G, S = solver.pencil_shape
    say("built", ops=type(ops).__name__, G=int(G), S=int(S),
        q=getattr(ops, "q", None), NB=getattr(ops, "NB", None),
        n_pad=getattr(ops, "n_pad", None),
        dsel=[len(solver.M_mat.dsel), len(solver.L_mat.dsel)]
        if hasattr(solver.M_mat, "dsel") else None,
        split=ts._split, plan=str(getattr(solver, "_solve_plan", None)),
        fused_solve=getattr(ops, "_fused_solve", None),
        build_phases=solver.build_phases.record(), memory=memory())
    dt = dep.fixed_dt
    rd = solver.real_dtype

    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        chip = SingleDeviceSharding(topo.devices[0])
        sds = lambda a: jax.ShapeDtypeStruct(        # noqa: E731
            np.shape(a), a.dtype, sharding=chip)
        tree = lambda t: jax.tree.map(sds, t)        # noqa: E731
        scalar = jax.ShapeDtypeStruct((), rd, sharding=chip)
        M, L, X = tree(solver.M_mat), tree(solver.L_mat), tree(solver.X)
        extra = tree(solver.rhs_extra())
        write, store, C, Gc = ops.incremental_chunk_program(solver.M_mat,
                                                            solver.L_mat)
        chunk = lambda a: None if a is None else jax.ShapeDtypeStruct(  # noqa: E731,E501
            (Gc,) + a.shape[1:], a.dtype, sharding=chip)
        aux = ops._aux_from_core(tree(store), {"ab": (scalar, scalar)})
        index = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        programs = {
            "factor_chunk": (write, (tree(store), index, chunk(M.bands),
                                     chunk(L.bands), chunk(M.Vt),
                                     chunk(L.Vt), scalar, scalar)),
            "mx0": (ts._mx0, (M, X)),
            "stage_eval": (ts._stage_eval, (M, L, X, scalar, extra)),
            "stage_solve_1": (ts._stage_solve, (1, X, [X], [X], scalar,
                                                aux, M, L)),
            "stage_solve_2": (ts._stage_solve, (2, X, [X, X], [X, X],
                                                scalar, aux, M, L)),
        }
        say("shapes", chunks=C, Gc=Gc, factor_store_MB=round(sum(
            np.prod(s.shape) * s.dtype.itemsize
            for s in jax.tree.leaves(store)) / 1e6, 1))
        for name, (program, pargs) in programs.items():
            t1 = time.time()
            try:
                mem = program.lower(*pargs).compile().memory_analysis()
                say("compiled", program=name, s=round(time.time() - t1, 1),
                    **{k: round(getattr(mem, f"{k}_size_in_bytes") / 1e6, 1)
                       for k in ("argument", "output", "temp", "alias")})
            except Exception as exc:
                say("refused", program=name, error=f"{type(exc).__name__}: "
                    f"{str(exc)[:1500]}")
        return 0

    t1 = time.time()
    ts._ensure_factor(dt)
    jax.block_until_ready(ts._lhs_aux)
    say("factored", s=round(time.time() - t1, 1), chunks=ops._g_chunks,
        aux_MB={k: round(sum(x.size * x.dtype.itemsize for x in
                             jax.tree.leaves(v)) / 1e6, 1)
                for k, v in ts._lhs_aux[0].items()}, memory=memory())
    for i in range(args.steps):
        t1 = time.time()
        solver.step(dt)
        jax.block_until_ready(solver.X)
        say("step", i=i, s=round(time.time() - t1, 3), memory=memory())
    t1 = time.time()
    solver.step_many(10, dt)
    jax.block_until_ready(solver.X)
    block_s = time.time() - t1
    t1 = time.time()
    solver.step_many(10, dt)
    jax.block_until_ready(solver.X)
    say("step_many", first_s=round(block_s, 3),
        second_s=round(time.time() - t1, 3), memory=memory())
    finite = bool(np.isfinite(np.asarray(solver.X)).all())
    t1 = time.time()
    invariants = dep.invariants()
    say("checked", finite=finite, s=round(time.time() - t1, 1),
        invariants={k: v[0] for k, v in invariants.items()},
        memory=memory())
    return 0 if finite else 1


if __name__ == "__main__":
    sys.exit(main())
