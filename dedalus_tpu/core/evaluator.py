"""
Evaluator and output handlers (reference: dedalus/core/evaluator.py).

Handlers own lists of tasks (symbolic expressions) evaluated on wall-time /
sim-time / iteration cadences (reference: core/evaluator.py:248-278
check_schedule). The reference's layout-oscillation machinery
(evaluate_handlers :94-148) is unnecessary here: expression trees evaluate
as jnp programs with shared-transform memoization.

FileHandler writes HDF5 with the reference's file schema (tasks/<name>,
scales/sim_time|iteration|write_number|timestep) so checkpoint restart and
post-processing tooling are format-compatible.
"""

import os
import pathlib
import logging
import numpy as np

from .field import Field
from .future import Future
from ..tools import tracing

logger = logging.getLogger(__name__)


class Evaluator:
    """Coordinates scheduled evaluation of handler tasks
    (reference: core/evaluator.py:30 Evaluator)."""

    def __init__(self, solver):
        self.solver = solver
        self.handlers = []

    def add_dictionary_handler(self, **kw):
        handler = DictionaryHandler(self.solver, **kw)
        self.handlers.append(handler)
        return handler

    def add_file_handler(self, base_path, **kw):
        handler = FileHandler(self.solver, base_path, **kw)
        self.handlers.append(handler)
        return handler

    def evaluate_scheduled(self, iteration=0, wall_time=0.0, sim_time=0.0,
                           timestep=None, **kw):
        due = [h for h in self.handlers
               if h.check_schedule(iteration=iteration, wall_time=wall_time,
                                   sim_time=sim_time)]
        if not due:
            return
        with tracing.span("step/handlers", {"n_due": len(due)}):
            self.evaluate_handlers(due, iteration=iteration,
                                   wall_time=wall_time, sim_time=sim_time,
                                   timestep=timestep)

    def evaluate_handlers(self, handlers=None, iteration=0, wall_time=0.0,
                          sim_time=0.0, timestep=None, **kw):
        if handlers is None:
            handlers = self.handlers
        for handler in handlers:
            handler.process(iteration=iteration, wall_time=wall_time,
                            sim_time=sim_time, timestep=timestep)


class Handler:
    """Task list with a schedule (reference: core/evaluator.py:209 Handler)."""

    def __init__(self, solver, group=None, wall_dt=None, sim_dt=None,
                 iter=None, custom_schedule=None):
        self.solver = solver
        self.tasks = []
        self.group = group
        self.wall_dt = wall_dt
        self.sim_dt = sim_dt
        self.iter = iter
        self.custom_schedule = custom_schedule
        self.last_wall_div = -1
        self.last_sim_div = -1
        self.last_iter_div = -1
        # optional transient-IO retry policy (tools/resilience.RetryPolicy
        # or any callable-with-.call) applied around file writes; None
        # writes directly (zero overhead beyond one attribute check)
        self.io_retry = None

    def schedule_state(self):
        """Scheduling counters as a restorable dict — captured into
        resilience snapshots (tools/resilience.py) so a rewound run
        re-arms its output cadences consistently with the rewound clock
        instead of skipping the replayed interval's writes."""
        return {"last_wall_div": self.last_wall_div,
                "last_sim_div": self.last_sim_div,
                "last_iter_div": self.last_iter_div}

    def restore_schedule_state(self, state):
        self.last_wall_div = state["last_wall_div"]
        self.last_sim_div = state["last_sim_div"]
        self.last_iter_div = state["last_iter_div"]

    def add_task(self, task, layout="g", name=None, scales=None):
        """Add a task (operand expression, field, or namespace string)."""
        if isinstance(task, str):
            namespace = self.solver.problem.namespace
            name = name or task
            task = eval(task, {}, namespace)
        if name is None:
            name = getattr(task, "name", None) or str(task)
        self.tasks.append({"operator": task, "layout": layout, "name": name,
                           "scales": scales})

    def add_tasks(self, tasks, **kw):
        for task in tasks:
            self.add_task(task, **kw)

    def add_system(self, system, **kw):
        self.add_tasks(system, **kw)

    def check_schedule(self, iteration=0, wall_time=0.0, sim_time=0.0):
        """Divisor-crossing cadence logic (reference: core/evaluator.py:248)."""
        scheduled = False
        if self.wall_dt is not None:
            div = int(wall_time // self.wall_dt)
            if div > self.last_wall_div:
                scheduled = True
                self.last_wall_div = div
        if self.sim_dt is not None:
            div = int((sim_time + 1e-12) // self.sim_dt)
            if div > self.last_sim_div:
                scheduled = True
                self.last_sim_div = div
        if self.iter is not None:
            div = iteration // self.iter
            if div > self.last_iter_div:
                scheduled = True
                self.last_iter_div = div
        if self.custom_schedule is not None:
            scheduled = scheduled or self.custom_schedule(
                iteration=iteration, wall_time=wall_time, sim_time=sim_time)
        return scheduled

    def _compile_tasks(self):
        """
        One compiled program evaluating every task of this handler under a
        shared memo, with all Field atoms as inputs: shared subexpressions
        and transforms are computed once per pass instead of once per task
        (reference batches tasks through grouped layout walks,
        core/evaluator.py:94-148).
        """
        from .future import EvalContext, CompiledWithFallback
        from .field import transform_to_grid, mesh_transforms
        dist = self.solver.dist
        tasks = list(self.tasks)
        atoms = set()
        for task in tasks:
            atoms |= task["operator"].atoms(Field)
        fields = sorted(atoms, key=lambda f: (f.name or "", id(f)))

        def fn(arrays):
            from ..tools.metrics import trace_scope
            with mesh_transforms(dist.mesh,
                                 chunks=getattr(self.solver,
                                                "_transpose_chunks", None)), \
                    trace_scope("evaluator", "tasks"):
                return fn_body(arrays)

        def fn_body(arrays):
            ctx = EvalContext(dict(zip(fields, arrays)))
            out = {}
            for task in tasks:
                op = task["operator"]
                if isinstance(op, Field):
                    data_c = ctx.field_data(op, "c")
                else:
                    data_c = op.ev(ctx, "c")
                if task["layout"] == "g":
                    scales = dist.remedy_scales(task["scales"] or 1)
                    tdim = len(op.tensorsig)
                    data = transform_to_grid(data_c, op.domain, scales, tdim,
                                             tensorsig=op.tensorsig)
                else:
                    data = data_c
                out[task["name"]] = data
            return out

        def eager():
            out = {}
            for task in tasks:
                op = task["operator"]
                field = op if isinstance(op, Field) else op.evaluate()
                if task["layout"] == "g":
                    field.change_scales(task["scales"] or 1)
                    out[task["name"]] = field["g"]
                else:
                    out[task["name"]] = field["c"]
            return out

        return CompiledWithFallback(fields, fn, eager,
                                    f"handler tasks {[t['name'] for t in tasks]}")

    def evaluate_tasks(self):
        """Evaluate all tasks, returning {name: numpy array}."""
        cache = getattr(self, "_task_cache", None)
        key = tuple((id(t["operator"]), t["layout"], t["scales"])
                    for t in self.tasks)
        if cache is None or cache["key"] != key:
            cache = self._task_cache = {"key": key,
                                        "runner": self._compile_tasks()}
        runner = cache["runner"]
        label = self._span_label()
        # the task program's inputs (the first read of a field after a
        # step launches the solver's scatter program: `state/scatter`) and
        # its own launch, or the eager walk after a fallback
        with tracing.span("handler/eval", {"handler": label}) as span:
            arrays = runner()
            span.set(mode=runner.mode)
        import jax
        to_global = np.asarray
        if jax.process_count() > 1:
            # multi-process world: device arrays spanning processes are
            # gathered collectively to a full copy on every process
            # (reference: per-process files + merge or gather modes,
            # dedalus/core/evaluator.py:656-846 — here the gather mode);
            # host arrays / single-process arrays are already global
            from ..parallel import multihost

            def to_global(v):
                if isinstance(v, jax.Array) and not v.is_fully_addressable:
                    return multihost.process_allgather(v)
                return np.asarray(v)

        # blocks until the device has produced the results
        with tracing.span("handler/pull", {"handler": label}) as span:
            results = {name: to_global(v) for name, v in arrays.items()}
            span.set(bytes=sum(v.nbytes for v in results.values()))
        return results

    def _span_label(self):
        """`<class>:<first task name>`, the `handler` attr of its spans."""
        first = self.tasks[0]["name"] if self.tasks else ""
        return f"{type(self).__name__}:{first}"

    def process(self, **kw):
        raise NotImplementedError


class DictionaryHandler(Handler):
    """Stores task results in a dict (reference: core/evaluator.py:325)."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.fields = {}

    def __getitem__(self, name):
        return self.fields[name]

    def process(self, **kw):
        self.fields.update(self.evaluate_tasks())


class FileHandler(Handler):
    """HDF5 output handler (reference: core/evaluator.py:369 H5FileHandler)."""

    def __init__(self, solver, base_path, max_writes=np.inf, mode=None, **kw):
        super().__init__(solver, **kw)
        from ..tools.config import config
        self.base_path = pathlib.Path(base_path)
        self.max_writes = max_writes
        self.mode = mode or config["analysis"].get("FILEHANDLER_MODE_DEFAULT",
                                                   "overwrite")
        self.set_num = 0
        self.write_num = 0
        self.current_file = None
        self.writes_in_set = 0
        from ..parallel import multihost
        self._primary = multihost.is_primary()
        if self._primary:
            os.makedirs(self.base_path, exist_ok=True)
        if self.mode == "append":
            # continue set and write numbering from existing output;
            # only the primary scans the (shared) filesystem, then the
            # bookkeeping is broadcast so every process numbers writes
            # identically (reference: core/evaluator.py:415-438)
            resume = 0
            if self._primary:
                self._scan_existing_sets()
                resume = int(self.current_file is not None)
            state = multihost.broadcast_from_primary(
                np.array([self.set_num, self.write_num,
                          self.writes_in_set, resume], dtype=np.int64))
            self.set_num, self.write_num, self.writes_in_set, resume = (
                int(v) for v in state)
            if resume and self.current_file is None:
                self.current_file = str(
                    self.base_path
                    / f"{self.base_path.name}_s{self.set_num}.h5")

    def _scan_existing_sets(self):
        from ..tools.post import get_assigned_sets
        existing = get_assigned_sets(self.base_path)
        if existing:
            import h5py
            self.set_num = int(existing[-1].stem.rsplit("_s", 1)[1])
            # scan back past empty/partial sets (e.g. from a crashed
            # run) so write_number stays globally unique
            for path in reversed(existing):
                with h5py.File(path, "r") as f:
                    if "scales/write_number" in f and len(f["scales/write_number"]):
                        self.write_num = int(np.asarray(f["scales/write_number"])[-1])
                        break
            # resume the last set if it still has room, instead of
            # opening a fresh under-filled set on every restart
            with h5py.File(existing[-1], "r") as f:
                writes = (len(f["scales/write_number"])
                          if "scales/write_number" in f else 0)
            if writes < self.max_writes:
                self.current_file = str(existing[-1])
                self.writes_in_set = writes

    def _new_file(self):
        import h5py
        self.set_num += 1
        self.writes_in_set = 0
        name = f"{self.base_path.name}_s{self.set_num}.h5"
        path = self.base_path / name
        self.current_file = str(path)
        if self._primary:
            with h5py.File(path, "w") as f:
                f.create_group("tasks")
                f.create_group("scales")
        return path

    def process(self, iteration=0, wall_time=0.0, sim_time=0.0, timestep=None, **kw):
        if self.current_file is None or self.writes_in_set >= self.max_writes:
            self._new_file()
        self.write_num += 1
        self.writes_in_set += 1
        # collective: every process participates in evaluation/gather;
        # only the primary touches the file below
        results = self.evaluate_tasks()
        if not self._primary:
            return
        write = lambda: self._write_results(results, iteration=iteration,
                                            wall_time=wall_time,
                                            sim_time=sim_time,
                                            timestep=timestep)
        with tracing.span("handler/write", {"handler": self._span_label()}):
            if self.io_retry is not None:
                # transient host/IO faults (flaky disk/NFS) retried with
                # backoff before they can kill the run (tools/resilience.py)
                self.io_retry.call(write, label=f"write {self.current_file}")
            else:
                write()

    def _write_results(self, results, iteration, wall_time, sim_time,
                       timestep):
        import h5py
        with h5py.File(self.current_file, "a") as f:
            scales = f["scales"]
            for key, val in [("sim_time", sim_time), ("wall_time", wall_time),
                             ("iteration", iteration),
                             ("write_number", self.write_num),
                             ("timestep", timestep if timestep is not None else np.nan)]:
                if key not in scales:
                    scales.create_dataset(key, shape=(0,), maxshape=(None,), dtype=np.float64)
                ds = scales[key]
                ds.resize((ds.shape[0] + 1,))
                ds[-1] = val
            tasks = f["tasks"]
            for name, data in results.items():
                if name not in tasks:
                    tasks.create_dataset(name, shape=(0,) + data.shape,
                                         maxshape=(None,) + data.shape,
                                         dtype=data.dtype)
                    task = next((t for t in self.tasks
                                 if t["name"] == name), None)
                    # recorded so load_state can restore through the
                    # layout the data was written in ('c' checkpoints
                    # round-trip bitwise — no transform in the path)
                    tasks[name].attrs["layout"] = \
                        task["layout"] if task else "g"
                    self._attach_grid_scales(f, tasks[name], name)
                ds = tasks[name]
                ds.resize((ds.shape[0] + 1,) + data.shape)
                ds[-1] = data

    def _attach_grid_scales(self, f, ds, name):
        """Store the task's grid arrays once and attach them as HDF5
        dimension scales (reference: core/evaluator.py:656-728 setup_file
        attaches per-axis scales), so post-processing (plot_snapshots,
        xarray) can recover coordinates from the file alone."""
        task = next((t for t in self.tasks if t["name"] == name), None)
        if task is None or task["layout"] != "g":
            return
        op = task["operator"]
        scales = self.solver.dist.remedy_scales(task["scales"] or 1)
        tdim = len(op.tensorsig)
        grp = f["scales"]
        dim = 0
        ds.dims[dim].label = "write"
        dim += 1
        for _ in range(tdim):
            ds.dims[dim].label = "component"
            dim += 1
        grids = []
        for axis, basis in enumerate(op.domain.bases):
            if basis is None:
                grids.append((f"const_{axis}", np.zeros(1)))
            elif basis.dim == 1:
                coord = basis.coord
                grids.append((coord.name, basis.global_grid(scales[axis])))
            else:
                sub = axis - basis.first_axis
                if sub == 0:
                    gs = basis.global_grids(
                        tuple(scales[basis.first_axis + i]
                              for i in range(basis.dim)))
                    for i, g in enumerate(gs):
                        grids.append((basis.cs.names[i], np.ravel(g)))
        import hashlib
        for gname, grid in grids:
            flat = np.ravel(grid)
            key = f"{gname}_{hashlib.sha1(flat.tobytes()).hexdigest()[:12]}"
            if key not in grp:
                grp.create_dataset(key, data=flat)
                grp[key].make_scale(gname)
            ds.dims[dim].attach_scale(grp[key])
            ds.dims[dim].label = gname
            dim += 1
