"""
The one traffic generator. A traffic mix is a data file under traffic/:

    advance            "step_many" (one scan block per unit of work) or
                       "step" (one solver.step per unit)
    block              steps per block, for step_many
    dt                 "fixed" (the configuration's fixed_dt) or "cfl" (the
                       configuration's CFL, asked before every step)
    loop               true: run the configuration's example loop pieces
                       (CFL, flow-property read, snapshots handler)
    output_sim_dt_scale  scales the handler's sim_dt (0.1 = ten times denser)
    spinup_sim_time    set-up advances to this sim_time by fixed-dt blocks
    spinup_block       steps per spin-up block
    warm_units         units run before the window, so that nothing
                       compiles inside it
    trace_units        units a traced run records

A unit of work is what the window counts in: `unit_size` iterations. Host spans go around the calls into the program, never inside
it; `span` is a no-op unless the run is traced.
"""

import contextlib
import math

import jax


class Driver:

    def __init__(self, params, deployment, output_dir, tracing=False):
        self.params = params
        self.dep = deployment
        self.solver = deployment.solver
        self.output_dir = output_dir
        self.tracing = tracing
        self.fixed_dt = float(deployment.fixed_dt)
        self.pieces = None
        self.dts = []          # every dt a step of the window was given
        # iterations one unit of work completes
        self.unit_size = int(params["block"]) \
            if params["advance"] == "step_many" else 1
        if params["advance"] not in ("step", "step_many"):
            raise ValueError(f"advance: {params['advance']!r}")
        if params["dt"] not in ("fixed", "cfl"):
            raise ValueError(f"dt: {params['dt']!r}")
        if params["dt"] == "cfl" and params["advance"] != "step":
            raise ValueError("dt 'cfl' needs advance 'step'")
        if params["dt"] == "cfl" and not params.get("loop"):
            raise ValueError("dt 'cfl' needs the configuration's loop")

    def span(self, name):
        if self.tracing:
            return jax.profiler.TraceAnnotation(f"chipbench/{name}")
        return contextlib.nullcontext()

    def spin_up(self):
        """Fixed-dt blocks up to spinup_sim_time (set-up, not measured)."""
        target = float(self.params.get("spinup_sim_time", 0.0))
        block = int(self.params.get("spinup_block", 50))
        left = target - self.solver.sim_time
        if left > 0:
            for _ in range(math.ceil(left / (block * self.fixed_dt))):
                self.solver.step_many(block, self.fixed_dt)
            jax.block_until_ready(self.solver.X)

    def start_loop(self):
        """The example's handler, CFL and flow property, from the developed
        state: CFL starts from the dt the spin-up used."""
        if not self.params.get("loop"):
            return
        self.pieces = self.dep.loop(
            initial_dt=self.fixed_dt, output_dir=self.output_dir,
            output_sim_dt_scale=float(
                self.params.get("output_sim_dt_scale", 1.0)))
        if self.tracing:
            # handler evaluation happens inside solver.step: wrap the call,
            # on this solver's evaluator only, so that its idle gaps carry
            # a name of their own
            evaluator = self.solver.evaluator
            inner = evaluator.evaluate_scheduled

            def evaluate_scheduled(*args, **kw):
                with self.span("handlers"):
                    return inner(*args, **kw)
            evaluator.evaluate_scheduled = evaluate_scheduled

    def unit(self):
        """One unit of work: `unit_size` iterations."""
        solver = self.solver
        if self.params["advance"] == "step_many":
            with self.span("block"):
                solver.step_many(self.unit_size, self.fixed_dt)
                jax.block_until_ready(solver.X)
            self.dts.append(self.fixed_dt)
            return
        pieces = self.pieces
        if self.params["dt"] == "cfl":
            with self.span("compute_timestep"):
                dt = pieces["cfl"].compute_timestep()
        else:
            dt = self.fixed_dt
        with self.span("step"):
            solver.step(dt)
        self.dts.append(float(dt))
        if pieces and pieces["read"] is not None \
                and (solver.iteration - 1) % pieces["read_every"] == 0:
            with self.span("flow_read"):
                pieces["read"]()

    def warm(self):
        """Everything the window will run, once, before it."""
        self.spin_up()
        self.start_loop()
        for _ in range(int(self.params.get("warm_units", 1))):
            self.unit()
        jax.block_until_ready(self.solver.X)
        self.dts.clear()
