"""
Compiled-program contract checker: the second static-analysis tier.

The AST rules (rules.py) catch hazards in Python source; the invariants
this framework's performance claims actually rest on live in COMPILED
program text — "zero full-state all-gathers in the sharded step", "no
triangular/pivot solves in the fused substitution scan", "the donated
history buffers really alias the outputs". Each was enforced by a one-off
regex buried in a single test, so any new program shape (a new scenario
builder, a new mesh composition, a pool-served fleet) shipped unchecked.
This module lowers a CENSUS of representative programs — the same
lifted_jit/jit wrappers the step loops dispatch, via the program handles
the owning modules expose (core/timesteppers.step_program_handle,
EnsembleSolver.step_program_handle, DifferentiableIVP.grad_program_handle)
— and checks each against a registry of declarative CONTRACTS over two
stable views of the program:

  * the COMPILED HLO text (`program.lower(*args).compile().as_text()`):
    collective placement (all-gather/all-to-all ops with their buffer
    sizes) and the `input_output_alias` donation header;
  * the JAXPR (`program.jaxpr(*args)` / `jax.make_jaxpr`): primitive-
    level structure — forbidden solve/callback primitives, and `pad`
    primitives inside partial-auto shard_map regions (the jaxlib-0.4.37
    SPMD-partitioner crash class PR 13 fixed by `tools.array.zeropad`).

Contracts (ids DTP1xx, disjoint from the AST DTL0xx ids):

  DTP101 no-full-state-gather   — size-aware: no all-gather whose result
                                  buffer reaches GATHER_FRACTION of the
                                  program's global state size. Small
                                  gathers (e.g. a tau line round-trip)
                                  pass; the full-state degradation the
                                  weak-scaling claim forbids fails.
  DTP102 no-forbidden-custom-call — no host-callback primitives/targets
                                  in any step/grad body; no triangular/
                                  pivot-LU solve primitives or LAPACK/
                                  cusolver custom calls in programs
                                  declared fused_solve (the 2.13x fusion
                                  win is precisely their absence).
  DTP103 collective-census      — at least the declared all-to-all count
                                  (one per chunk per transpose stage): a
                                  GSPMD fallback that silently replaces a
                                  chunked exchange with a gather is a
                                  lint failure, not a perf mystery.
  DTP104 donation-honored       — programs declaring donated buffers
                                  must compile with that many
                                  input_output_alias entries; a dropped
                                  donation is a silent 3x-state memory
                                  regression.
  DTP105 manual-region-integrity — no `pad` primitives inside shard_map
                                  regions with a nonempty `auto` set
                                  (pads in FULLY manual regions are
                                  explicitly partitioned and safe; pads
                                  in the GSPMD-auto subregion of a
                                  partially-manual shard_map are the
                                  hard-crash class).
  DTP107 tracing-inert          — programs declaring an untraced-build
                                  HLO hash (meta["untraced_sha256"])
                                  must compile byte-identically with
                                  request tracing enabled: the
                                  observability layer (tools/tracing.py)
                                  is host-side bookkeeping by contract,
                                  and a span helper leaking into the
                                  lowered computation is a lint failure,
                                  not a perf mystery.

Findings reuse the lint framework's Finding/baseline discipline
(framework.py): keys are (contract, "__programs__/<name>", detail), the
grandfather baseline lives in progcheck_baseline.json (empty on a healthy
tree), and per-program waivers declared in the census are counted as
suppressions, never silently dropped. The census runs CPU-only on the
virtual-device mesh (`--xla_force_host_platform_device_count`), so CI
needs no chip; builders that need more devices than the process has are
reported as skipped, not silently absent.

Entry points: `python -m dedalus_tpu lint --programs` (cli.py) and
`run_programs()` (tests/test_progcheck.py, the tier-1 gate).
"""

import hashlib
import pathlib
import re
import time

import numpy as np

from .framework import (Finding, PACKAGE_DIR, apply_baseline,
                        load_baseline)

# the checked-in grandfather baseline for PROGRAM findings (kept separate
# from the AST baseline: the two tiers regenerate independently)
PROGRAMS_BASELINE = PACKAGE_DIR / "tools" / "lint" / "progcheck_baseline.json"

# pseudo-path root for program findings: baseline keys come out as
# "__programs__/<census name>", stable across checkouts like the
# package-relative source paths of AST findings
_PSEUDO_ROOT = PACKAGE_DIR / "__programs__"

# an all-gather counts as "full-state" when one gathered buffer reaches
# this fraction of the program's global state size (tau-line round-trips
# and tiny bookkeeping gathers stay legal; gathering the pencil state
# does not)
GATHER_FRACTION = 0.5

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

__all__ = ["ProgramRecord", "Contract", "all_contracts", "collective_counts",
           "gather_buffers", "donated_alias_count", "jaxpr_primitives",
           "pads_in_auto_regions", "scan_lengths", "record_from_jit",
           "register_contract", "run_census", "check_records",
           "run_programs", "census_names", "PROGRAMS_BASELINE",
           "GATHER_FRACTION", "program_ledger", "hlo_instruction_count",
           "ledger_rows", "append_ledger_rows", "LEDGER_FIELDS"]


# ------------------------------------------------------- program analyses

def collective_counts(hlo_text):
    """Collective-op census of a compiled HLO module. The SHARED parser
    behind tests/test_collectives.py, tests/test_distributed.py and the
    DTP101/DTP103 contracts (each test used to carry its own regex)."""
    return {op: len(re.findall(rf"\s{op}(?:-start)?\(", hlo_text))
            for op in ("all-to-all", "all-gather", "all-reduce",
                       "reduce-scatter", "collective-permute")}


def _shape_bytes(dtype, dims):
    width = _DTYPE_BYTES.get(dtype)
    if width is None:
        return None
    n = 1
    for d in dims.split(",") if dims else []:
        if d:
            n *= int(d)
    return n * width


def gather_buffers(hlo_text):
    """[(dtype, shape, nbytes)] for every buffer produced by an
    all-gather op in the compiled module (tuple-shaped gathers yield one
    entry per element). Sizes are the gathered RESULT shapes — exactly
    what lands on every device."""
    out = []
    for line in hlo_text.splitlines():
        if " all-gather(" not in line and " all-gather-start(" not in line:
            continue
        head = line.split(" all-gather", 1)[0]
        if "=" not in head:
            continue
        head = head.split("=", 1)[1]
        for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", head):
            nbytes = _shape_bytes(dtype, dims)
            if nbytes is not None:
                out.append((dtype, dims, nbytes))
    return out


def donated_alias_count(hlo_text):
    """Number of input/output alias pairs in the compiled module header —
    donation that XLA actually honored. A donate_argnums the compiler
    dropped (shape mismatch, aliasing conflict) simply does not appear
    here, which is exactly what DTP104 exists to catch."""
    header = hlo_text.split("\n", 1)[0]
    m = re.search(r"input_output_alias=\{(.*)", header)
    if not m:
        return 0
    return len(re.findall(r"\{[\d,\s]*\}:\s*\(\d+", m.group(1)))


def _walk_jaxprs(jaxpr, visit, in_auto=False):
    """Depth-first over a (Closed)Jaxpr and every sub-jaxpr reachable
    through eqn params (pjit bodies, scan/while bodies, cond branches,
    custom_vjp calls, shard_map regions). `visit(eqn, in_auto)` sees each
    equation with whether it sits inside a shard_map region that leaves
    some mesh axis out of `manual_axes` (the partially-manual GSPMD
    region)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        visit(eqn, in_auto)
        sub_auto = in_auto
        if eqn.primitive.name == "shard_map":
            sub_auto = bool(set(eqn.params["mesh"].axis_names)
                            - set(eqn.params["manual_axes"]))
        for val in eqn.params.values():
            items = val if isinstance(val, (list, tuple)) else [val]
            for item in items:
                if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                    _walk_jaxprs(item, visit, sub_auto)


def jaxpr_primitives(jaxpr):
    """{primitive name: count} over the whole program, sub-jaxprs
    included."""
    counts = {}

    def visit(eqn, _):
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1

    _walk_jaxprs(jaxpr, visit)
    return counts


def scan_lengths(jaxpr):
    """(lengths, whiles): the trip count of every `scan` equation in the
    program (sub-jaxprs included) and the number of `while` equations
    (whose trip counts are unprovable from the program text). The DTP106
    depth analysis: a restructured substitution's sequential depth IS
    the longest scan left in its lowered program."""
    lengths = []
    whiles = [0]

    def visit(eqn, _):
        if eqn.primitive.name == "scan":
            length = eqn.params.get("length")
            if length is not None:
                lengths.append(int(length))
        elif eqn.primitive.name == "while":
            whiles[0] += 1

    _walk_jaxprs(jaxpr, visit)
    return lengths, whiles[0]


def pads_in_auto_regions(jaxpr):
    """Count of `pad` primitives lexically inside shard_map regions with
    a nonempty `auto` set. Pads inside FULLY manual regions are already
    partitioned by hand and lower fine; pads the GSPMD partitioner must
    propagate shardings through inside a partial-auto region hard-crash
    jaxlib 0.4.37 (hlo_sharding_util CHECK IsManualSubgroup) — the class
    tools.array.zeropad exists to keep out of traced bodies."""
    hits = [0]

    def visit(eqn, in_auto):
        if in_auto and eqn.primitive.name == "pad":
            hits[0] += 1

    _walk_jaxprs(jaxpr, visit)
    return hits[0]


# ------------------------------------------------------ the resource ledger

# one HLO instruction per `name = type[...] op(...)` line (ROOT-prefixed
# or %-sigiled in older dumps); computation headers/braces don't match
_HLO_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s", re.M)

# every quantitative field a ledger can carry, all nullable: a backend
# lacking (or raising from) cost_analysis/memory_analysis degrades to
# partial rows, never a failed census
LEDGER_FIELDS = ("flops", "transcendentals", "bytes_accessed",
                 "argument_bytes", "output_bytes", "temp_bytes",
                 "generated_code_bytes", "peak_bytes", "hlo_instructions")


def hlo_instruction_count(hlo_text):
    """Instruction count of a compiled HLO module — the cheapest stable
    proxy for compiled-program size (tracks fusion regressions that flops
    alone cannot: an unfused program re-materializes as more
    instructions, not more arithmetic)."""
    return len(_HLO_INSTR_RE.findall(hlo_text or ""))


def _as_cost_dict(cost):
    """cost_analysis() returns a flat dict on current jax and a
    list-of-dicts (one per computation, main first) on older releases."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    return cost if isinstance(cost, dict) else None


def program_ledger(compiled, hlo_text=None):
    """Resource ledger of one compiled program: XLA ``cost_analysis()``
    (flops, transcendentals, bytes accessed) and ``memory_analysis()``
    (argument/output/temp/code bytes, with ``peak_bytes`` derived as
    their alias-corrected sum) plus the HLO instruction count. Every
    probe is guarded: a backend where an analysis is absent or raises
    yields nulls for its fields — the census stays green, the trajectory
    row records the absence explicitly."""
    ledger = {"ledger_version": 1}
    ledger.update({field: None for field in LEDGER_FIELDS})
    try:
        cost = _as_cost_dict(compiled.cost_analysis())
    except Exception:
        cost = None
    if cost:
        for field, key in (("flops", "flops"),
                           ("transcendentals", "transcendentals"),
                           ("bytes_accessed", "bytes accessed")):
            try:
                value = cost.get(key)
                if value is not None:
                    ledger[field] = int(value)
            except (TypeError, ValueError):
                pass
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    if mem is not None:
        for field, attr in (
                ("argument_bytes", "argument_size_in_bytes"),
                ("output_bytes", "output_size_in_bytes"),
                ("temp_bytes", "temp_size_in_bytes"),
                ("generated_code_bytes", "generated_code_size_in_bytes")):
            try:
                ledger[field] = int(getattr(mem, attr))
            except Exception:
                pass
        sized = [ledger[f] for f in
                 ("argument_bytes", "output_bytes", "temp_bytes")]
        if any(v is not None for v in sized):
            try:
                alias = int(getattr(mem, "alias_size_in_bytes"))
            except Exception:
                alias = 0
            ledger["peak_bytes"] = max(
                sum(v or 0 for v in sized) - alias, 0)
    if hlo_text is not None:
        ledger["hlo_instructions"] = hlo_instruction_count(hlo_text)
    return ledger


def _compile_views(lowered):
    """(hlo_text, ledger) off ONE compile of a lowered program — the
    census must never pay a second XLA compile just to read costs."""
    compiled = lowered.compile()
    text = compiled.as_text()
    return text, program_ledger(compiled, hlo_text=text)


def _plan_of(solver):
    """Guarded plan provenance: a handle without plan_provenance() (or
    one that raises during lowering-time introspection) yields None —
    rendered downstream as plan=unversioned, never faked."""
    try:
        return solver.plan_provenance()
    except Exception:
        return None


# ------------------------------------------------------------ the records

class ProgramRecord:
    """One lowered census program plus the metadata contracts key on.

    meta keys (all optional; a contract that needs one it lacks does not
    apply):
      sharded: bool            — collective contracts apply
      state_bytes: int         — global state size for the gather bound
      expected_a2a_min: int    — declared all-to-all floor (DTP103)
      donated: int             — declared donated-buffer count (DTP104)
      fused_solve: bool        — triangular/pivot solves forbidden
      manual_auto: bool        — program carries a partial-auto shard_map
                                 (informational; DTP105 walks every jaxpr)
      waive: set[str]          — contract ids waived for this program
                                 (counted as suppressed, never dropped)
    """

    __slots__ = ("name", "description", "compiled_text", "jaxpr", "meta",
                 "build_sec", "skipped", "ledger", "plan")

    def __init__(self, name, description="", compiled_text=None, jaxpr=None,
                 meta=None, build_sec=0.0, skipped=None, ledger=None,
                 plan=None):
        self.name = name
        self.description = description
        self.compiled_text = compiled_text
        self.jaxpr = jaxpr
        self.meta = dict(meta or {})
        self.build_sec = build_sec
        self.skipped = skipped
        self.ledger = ledger      # program_ledger() dict (None: not costed)
        self.plan = plan          # plan_provenance() dict (None: no plan)

    def pseudo_path(self):
        return _PSEUDO_ROOT / f"{self.name}.hlo"

    def stats(self):
        """Per-program census row for the JSON report."""
        row = {"program": self.name, "build_sec": round(self.build_sec, 3)}
        if self.skipped:
            row["skipped"] = self.skipped
            return row
        if self.compiled_text is not None:
            row["collectives"] = collective_counts(self.compiled_text)
            row["donated_aliases"] = donated_alias_count(self.compiled_text)
        if self.jaxpr is not None:
            row["pads_in_auto_regions"] = pads_in_auto_regions(self.jaxpr)
            if "max_scan_length" in self.meta:
                lengths, whiles = scan_lengths(self.jaxpr)
                row["scan_lengths"] = sorted(set(lengths), reverse=True)
                row["while_loops"] = whiles
        for key in ("state_bytes", "expected_a2a_min", "donated",
                    "fused_solve", "manual_auto", "max_scan_length",
                    "untraced_sha256"):
            if key in self.meta:
                row[key] = self.meta[key]
        if self.ledger is not None:
            row["ledger"] = self.ledger
        return row


def record_from_jit(name, fn, args, meta=None, donate_argnums=(),
                    description="", compile=True):
    """Build a ProgramRecord from a plain function: jit (with the given
    donation), compile, and capture the jaxpr. The fixture surface the
    seeded-regression tests drive contracts with — and the documented way
    to census a new program shape that has no package handle yet.
    `compile=False` captures the jaxpr only: the DTP105 crash class
    ABORTS the process inside the XLA partitioner (a CHECK failure, not
    an exception), so a program seeded with it can only be inspected at
    the jaxpr level — which is exactly the tier the contract runs at."""
    import jax
    t0 = time.perf_counter()
    compiled_text = ledger = None
    if compile:
        lowered = jax.jit(  # dedalus-lint: disable=DTL003 (one-shot fixture lowering, never dispatched)
            fn, donate_argnums=donate_argnums).lower(*args)
        compiled_text, ledger = _compile_views(lowered)
    jaxpr = jax.make_jaxpr(fn)(*args)
    return ProgramRecord(name, description=description,
                         compiled_text=compiled_text, jaxpr=jaxpr,
                         meta=meta, build_sec=time.perf_counter() - t0,
                         ledger=ledger)


# ---------------------------------------------------------- the contracts

CONTRACTS = {}


def register_contract(cls):
    CONTRACTS[cls.id] = cls()
    return cls


def all_contracts():
    return [CONTRACTS[cid] for cid in sorted(CONTRACTS)]


class Contract:
    """Base contract: subclasses set id/severity/title and implement
    check(record) yielding Findings (same Finding type as the AST rules,
    so the baseline/JSON machinery is shared)."""

    id = None
    severity = "error"
    title = ""

    def check(self, record):
        raise NotImplementedError

    def finding(self, record, detail, message):
        """`detail` is the stable baseline-key snippet (survives line
        drift by construction: program findings have no lines)."""
        return Finding(self.id, self.severity, record.pseudo_path(), 1, 0,
                       f"[{record.name}] {message}", detail)


@register_contract
class NoFullStateGather(Contract):
    """DTP101: no all-gather at global state size in sharded programs.

    The weak-scaling claim (benchmarks/scaling.py, docs/performance.md)
    rests on the sharded step moving pencils with all-to-all transposes;
    XLA's SPMD partitioner degrades unpartitionable ops (ffts, LU custom
    calls) to all-gather + replicated compute SILENTLY — correct numerics,
    destroyed memory/scaling. Size-aware: a gather is a violation when one
    gathered buffer reaches GATHER_FRACTION of meta["state_bytes"]; the
    tau-line round-trips of the 2-D fleet composition
    (meshctx.gathered_apply) stay legal because the lines are small.
    """

    id = "DTP101"
    severity = "error"
    title = "no-full-state-gather"

    def check(self, record):
        if not record.meta.get("sharded") or record.compiled_text is None:
            return
        state = int(record.meta.get("state_bytes", 0))
        if not state:
            return
        for dtype, dims, nbytes in gather_buffers(record.compiled_text):
            if nbytes >= GATHER_FRACTION * state:
                yield self.finding(
                    record, f"all-gather {dtype}[{dims}]",
                    f"full-state all-gather of {dtype}[{dims}] "
                    f"({nbytes} B >= {GATHER_FRACTION:.0%} of the "
                    f"{state} B global state): a shard_map/sharding-"
                    "constraint route has regressed to GSPMD replication")


@register_contract
class NoForbiddenCustomCall(Contract):
    """DTP102: forbidden primitives/custom calls in step and grad bodies.

    Host callbacks have no transpose rule and serialize dispatch — they
    must never compile into a step or grad program (the runtime telemetry
    reads device buffers on a cadence instead). Programs declared
    meta["fused_solve"] additionally forbid triangular/pivot solve
    primitives: the fused substitution (core/fusedstep.py) precomposes
    the panel factors into GEMMs precisely so no solve_triangular custom
    call (measured ~19x an equivalent matmul) survives in the scan.
    """

    id = "DTP102"
    severity = "error"
    title = "no-forbidden-custom-call"

    _CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                      "callback", "outside_call")
    _SOLVE_PRIMS = ("triangular_solve", "lu", "lu_pivots_to_permutation",
                    "custom_linear_solve")
    _CALLBACK_TARGETS = re.compile(r"callback|CpuCallback|py_func",
                                   re.IGNORECASE)
    _SOLVE_TARGETS = re.compile(
        r"lapack_\w*(getrf|trsm|gesv)|cusolver|cublas_\w*trsm")

    def check(self, record):
        prims = jaxpr_primitives(record.jaxpr) if record.jaxpr is not None \
            else {}
        for prim in self._CALLBACK_PRIMS:
            if prims.get(prim):
                yield self.finding(
                    record, f"primitive {prim}",
                    f"host callback primitive '{prim}' ({prims[prim]}x) "
                    "compiled into the program body: no transpose rule, "
                    "serializes dispatch; hoist the host work out of the "
                    "traced body")
        if record.meta.get("fused_solve"):
            for prim in self._SOLVE_PRIMS:
                if prims.get(prim):
                    yield self.finding(
                        record, f"primitive {prim}",
                        f"'{prim}' ({prims[prim]}x) inside a fused-"
                        "substitution program: the precomposed GEMM path "
                        "(core/fusedstep.py FUSED_SOLVE) has regressed to "
                        "per-step triangular/pivot solves")
        if record.compiled_text is None:
            return
        targets = set(re.findall(r'custom_call_target="([^"]+)"',
                                 record.compiled_text))
        for target in sorted(targets):
            if self._CALLBACK_TARGETS.search(target):
                yield self.finding(
                    record, f"custom-call {target}",
                    f"host-callback custom call '{target}' in the "
                    "compiled program body")
            elif record.meta.get("fused_solve") \
                    and self._SOLVE_TARGETS.search(target):
                yield self.finding(
                    record, f"custom-call {target}",
                    f"solver custom call '{target}' inside a fused-"
                    "substitution program")


@register_contract
class CollectiveCensus(Contract):
    """DTP103: the declared all-to-all floor per program.

    Chunked transpose stages (parallel/transposes.py) compile one
    all_to_all per chunk; a GSPMD fallback that re-routes a stage through
    gather + replicated transform REMOVES all-to-alls (DTP101 catches the
    gather only when it is state-sized — a per-stage degradation on a
    small axis can hide below that bound, but never below this count).
    """

    id = "DTP103"
    severity = "error"
    title = "collective-census"

    def check(self, record):
        expected = record.meta.get("expected_a2a_min")
        if expected is None or record.compiled_text is None:
            return
        got = collective_counts(record.compiled_text)["all-to-all"]
        if got < int(expected):
            yield self.finding(
                record, f"all-to-all {got} < {int(expected)}",
                f"{got} all-to-all op(s) compiled where the census "
                f"declares >= {int(expected)} (one per chunk per "
                "transpose stage): a chunked exchange degraded to a "
                "gather/replicated path")


@register_contract
class DonationHonored(Contract):
    """DTP104: declared donations must appear as input_output_alias.

    The fused multistep programs donate the three history buffers
    (F/MX/LX) so XLA rolls them in place; XLA silently DROPS a donation
    it cannot honor (layout mismatch, an aliasing conflict introduced by
    a refactor), turning a zero-copy update into three fresh state-sized
    allocations per step. lifted_jit.lower carries donate_argnums through
    precisely so this header is checkable.
    """

    id = "DTP104"
    severity = "error"
    title = "donation-honored"

    def check(self, record):
        expected = record.meta.get("donated")
        if not expected or record.compiled_text is None:
            return
        got = donated_alias_count(record.compiled_text)
        if got < int(expected):
            yield self.finding(
                record, f"aliases {got} < {int(expected)}",
                f"{got} input_output_alias entr"
                f"{'y' if got == 1 else 'ies'} compiled where "
                f"{int(expected)} donated buffer(s) are declared: a "
                "donation was dropped (silent per-step memory "
                "regression; check donate_argnums wiring and buffer "
                "aliasing)")


@register_contract
class ManualRegionIntegrity(Contract):
    """DTP105: no pad primitives inside partial-auto shard_map regions.

    jaxlib 0.4.37's SPMD partitioner hard-crashes (hlo_sharding_util
    CHECK IsManualSubgroup) propagating shardings through `pad` inside
    the GSPMD-auto subregion of a partially-manual shard_map — the region
    every per-member op of the 2-D batch x pencil fleet lives in. PR 13
    replaced the traced zero-pads with tools.array.zeropad (concat with
    zeros, bitwise identical); this contract detects a restored pad
    instead of letting the crash be rediscovered at the next mesh
    composition. Fully-manual regions are exempt: their pads are already
    explicitly partitioned.
    """

    id = "DTP105"
    severity = "error"
    title = "manual-region-integrity"

    def check(self, record):
        if record.jaxpr is None:
            return
        pads = pads_in_auto_regions(record.jaxpr)
        if pads:
            yield self.finding(
                record, f"pad-in-auto-region x{pads}",
                f"{pads} pad primitive(s) inside a partial-auto "
                "shard_map region (the jaxlib SPMD-partitioner crash "
                "class): lower zero padding through tools.array.zeropad, "
                "or route the op through an explicit manual shard_map")


@register_contract
class ScanDepthBound(Contract):
    """DTP106: the substitution depth claim, machine-checkable.

    The restructured solve compositions (libraries/solvecomp.py) exist
    to cut the banded substitution's sequential depth: ascan leaves NO
    sequential scan over the block rows (ceil(log2(NB))+1 bounds the
    residual-refinement loop and any bookkeeping scan), spike leaves
    exactly the C-step reduced coupling scan. A refactor that silently
    reintroduces an O(NB) lax.scan (or hides depth in a while loop,
    whose trip count is unprovable from the program text) would keep
    the numerics and lose the entire point — this contract fails it.
    Programs declare their bound via meta["max_scan_length"].
    """

    id = "DTP106"
    severity = "error"
    title = "scan-depth-bound"

    def check(self, record):
        bound = record.meta.get("max_scan_length")
        if bound is None or record.jaxpr is None:
            return
        lengths, whiles = scan_lengths(record.jaxpr)
        worst = max(lengths, default=0)
        if worst > int(bound):
            yield self.finding(
                record, f"scan length {worst} > {int(bound)}",
                f"a lax.scan of length {worst} compiled where the "
                f"declared substitution depth bound is {int(bound)}: "
                "the restructured solve has regressed to a sequential "
                "sweep (check SOLVE_COMPOSITION wiring and the "
                "solvecomp chunk/prefix programs)")
        if whiles:
            yield self.finding(
                record, f"while-loop x{whiles}",
                f"{whiles} while loop(s) in a depth-bounded program: "
                "trip counts are unprovable from the program text; use "
                "fixed-length lax.scan/fori_loop so the depth contract "
                "stays checkable")


@register_contract
class TracingInert(Contract):
    """DTP107: request tracing must not change the compiled program.

    The observability layer (tools/tracing.py, docs/observability.md)
    promises "structurally free when off, host-side only when on": spans
    wrap dispatch sites, never traced computations, so enabling tracing
    must leave the lowered step program byte-identical. A span helper
    that slips inside a jit boundary (or gates lowering on
    tracing.enabled()) would silently fork the compiled artifact and
    invalidate every cross-run comparison. Programs declare the
    tracing-DISABLED build's HLO hash via meta["untraced_sha256"]; the
    record's compiled_text is the tracing-ENABLED build of the same
    program."""

    id = "DTP107"
    severity = "error"
    title = "tracing-inert"

    def check(self, record):
        want = record.meta.get("untraced_sha256")
        if want is None or record.compiled_text is None:
            return
        got = hashlib.sha256(record.compiled_text.encode()).hexdigest()
        if got != want:
            yield self.finding(
                record, "traced/untraced HLO divergence",
                "the compiled step program differs between tracing "
                f"enabled (sha256 {got[:12]}) and disabled (sha256 "
                f"{want[:12]}): instrumentation has leaked into the "
                "lowered computation — spans must stay host-side "
                "(docs/observability.md)")


# ------------------------------------------------------------- the census

CENSUS = {}


def census(name, fast=True):
    """Register a census builder. `fast=False` marks the expensive
    builders (banded RB factor+fuse builds) excluded from the tier-1
    subset (tests/test_progcheck.py) but included in the full
    `lint --programs` run."""
    def wrap(fn):
        CENSUS[name] = (fn, bool(fast))
        return fn
    return wrap


def census_names(fast_only=False):
    return [n for n, (_, fast) in CENSUS.items() if fast or not fast_only]


class _pinned_config:
    """Pin config keys for one build (restored on exit): census programs
    must not depend on ambient [fusion]/[distributed] mutations."""

    def __init__(self, section, **keys):
        self.section = section
        self.keys = keys

    def __enter__(self):
        from ...tools.config import config
        if not config.has_section(self.section):
            config.add_section(self.section)
        self.saved = {k: config[self.section].get(k) for k in self.keys}
        for k, v in self.keys.items():
            config[self.section][k] = v

    def __exit__(self, *exc):
        from ...tools.config import config
        for k, v in self.saved.items():
            if v is None:
                config[self.section].pop(k, None)
            else:
                config[self.section][k] = v


def _solver_record(name, solver, description, extra_meta=None, dt=1e-3):
    """ProgramRecord of a solver's compiled step program via the
    timesteppers handle; donation expectation derives from the wrapper's
    own donate_argnums unless the builder pins it explicitly."""
    from ...core.timesteppers import step_program_handle
    prog, args = step_program_handle(solver, dt=dt)
    meta = {"donated": len(getattr(prog, "donate_argnums", ()))}
    meta.update(extra_meta or {})
    compiled_text, ledger = _compile_views(prog.lower(*args))
    jaxpr = prog.jaxpr(*args)
    return ProgramRecord(name, description=description,
                         compiled_text=compiled_text, jaxpr=jaxpr,
                         meta=meta, ledger=ledger, plan=_plan_of(solver))


def _need_devices(n):
    import jax
    have = len(jax.devices())
    if have < n:
        return (f"needs >= {n} devices, have {have} (set "
                "--xla_force_host_platform_device_count in XLA_FLAGS "
                "before JAX initializes)")
    return None


@census("diffusion_step")
def _census_diffusion_step():
    """Dense multistep (SBDF2) step program with donation pinned ON: the
    donation-honored anchor — the declared 3 history buffers (F/MX/LX)
    must alias outputs."""
    from ...extras.bench_problems import build_diffusion_solver
    with _pinned_config("fusion", DONATE_STEP="on"):
        solver = build_diffusion_solver(48)
        solver.step(1e-3)
        rec = _solver_record(
            "diffusion_step", solver,
            "dense SBDF2 diffusion step (donating multistep program)",
            extra_meta={"donated": 3})
    return [rec]


@census("rb_step_fused", fast=False)
def _census_rb_fused():
    """Banded Rayleigh-Benard step with FUSED_SOLVE pinned on: the
    precomposed-GEMM substitution — triangular/pivot solves forbidden."""
    from ...extras.bench_problems import build_rb_solver
    with _pinned_config("fusion", FUSED_SOLVE="on", FUSED_MATVEC="auto",
                        FUSED_TRANSFORMS="off", DONATE_STEP="auto"):
        solver, _ = build_rb_solver(16, 32, np.float64, matsolver="banded")
        solver.step(1e-3)
        rec = _solver_record(
            "rb_step_fused", solver,
            "banded RB RK222 step, fused substitution (no triangular/"
            "pivot solves)", extra_meta={"fused_solve": True})
    return [rec]


@census("rb_step_unfused", fast=False)
def _census_rb_unfused():
    """The same banded RB step with fusion off: breadth coverage (the
    unfused path legitimately carries triangular solves, so only the
    callback contract applies)."""
    from ...extras.bench_problems import build_rb_solver
    with _pinned_config("fusion", FUSED_SOLVE="off", FUSED_MATVEC="off",
                        FUSED_TRANSFORMS="off", DONATE_STEP="off"):
        solver, _ = build_rb_solver(16, 32, np.float64, matsolver="banded")
        solver.step(1e-3)
        rec = _solver_record(
            "rb_step_unfused", solver,
            "banded RB RK222 step, fusion off (legacy substitution)")
    return [rec]


@census("tau_step_ascan")
def _census_tau_ascan():
    """Banded tau-IVP step with the associative-scan substitution
    (SOLVE_COMPOSITION=ascan): no triangular/pivot solves (DTP102) AND
    no sequential scan over the block rows — the depth claim of the
    log-depth composition, bounded at ceil(log2(NB))+1 (DTP106). The
    small banded problem keeps this in the fast tier-1 subset."""
    import math
    from ...extras.bench_problems import build_tau_ivp
    with _pinned_config("fusion", FUSED_SOLVE="on", SOLVE_COMPOSITION="ascan"):
        solver, u, x, z = build_tau_ivp(8, 32, matsolver="banded")
        solver.step(1e-3)
        bound = math.ceil(math.log2(solver.ops.NB)) + 1
        rec = _solver_record(
            "tau_step_ascan", solver,
            "banded tau-IVP SBDF2 step, associative-scan substitution "
            f"(NB={solver.ops.NB}, depth bound {bound})",
            extra_meta={"fused_solve": True, "max_scan_length": bound})
    return [rec]


@census("rb_step_spike", fast=False)
def _census_rb_spike():
    """Banded Rayleigh-Benard step with the SPIKE-chunked substitution:
    the only sequential scan left is the C-step reduced coupling
    (DTP106 bound = C), and the chunk GEMM program still carries no
    triangular/pivot custom calls (DTP102)."""
    from ...extras.bench_problems import build_rb_solver
    from ...libraries import solvecomp
    with _pinned_config("fusion", FUSED_SOLVE="on", SOLVE_COMPOSITION="spike",
                        SPIKE_CHUNKS="auto"):
        solver, _ = build_rb_solver(16, 32, np.float64, matsolver="banded")
        solver.step(1e-3)
        chunks = solvecomp.spike_chunk_count(
            solver.ops.NB - 1, solver._solve_plan.spike_chunks)
        rec = _solver_record(
            "rb_step_spike", solver,
            f"banded RB RK222 step, SPIKE substitution (C={chunks})",
            extra_meta={"fused_solve": True, "max_scan_length": chunks})
    return [rec]


@census("rb_step_ladder", fast=False)
def _census_rb_ladder():
    """The precision-laddered banded RB step (SPIKE + f32 operators +
    f64 residual refinement): the fused-solve and depth contracts must
    survive the low-dtype factor store, and the fixed-trip refinement
    loop must stay inside the depth bound (no while loops)."""
    from ...extras.bench_problems import build_rb_solver
    from ...libraries import solvecomp
    with _pinned_config("fusion", FUSED_SOLVE="on", SOLVE_COMPOSITION="spike",
                        SPIKE_CHUNKS="auto"):
        with _pinned_config("precision", SOLVE_DTYPE="f32",
                            REFINE_SWEEPS="auto"):
            solver, _ = build_rb_solver(16, 32, np.float64,
                                        matsolver="banded")
            solver.step(1e-3)
            chunks = solvecomp.spike_chunk_count(
                solver.ops.NB - 1, solver._solve_plan.spike_chunks)
            sweeps = solver._solve_plan.sweeps or 0
            rec = _solver_record(
                "rb_step_ladder", solver,
                "banded RB RK222 step, f32 precision ladder over SPIKE "
                f"(C={chunks}, {sweeps} refinement sweeps)",
                extra_meta={"fused_solve": True,
                            "max_scan_length": max(chunks, sweeps)})
    return [rec]


@census("traced_step")
def _census_traced_step():
    """The dense diffusion step lowered twice — request tracing disabled,
    then enabled — with the disabled build's HLO hash declared in meta so
    DTP107 can assert the enabled build is byte-identical: the
    observability layer's zero-overhead-when-off claim as a
    machine-checked structural fact, not a benchmark delta."""
    from ...tools import tracing
    from ...extras.bench_problems import build_diffusion_solver
    from ...core.timesteppers import step_program_handle

    def compiled_step():
        solver = build_diffusion_solver(32)
        solver.step(1e-3)
        prog, args = step_program_handle(solver, dt=1e-3)
        meta = {"donated": len(getattr(prog, "donate_argnums", ()))}
        text, ledger = _compile_views(prog.lower(*args))
        return text, prog.jaxpr(*args), meta, ledger, _plan_of(solver)

    was_on = tracing.enabled()
    with _pinned_config("fusion", DONATE_STEP="on"):
        try:
            tracing.disable()
            off_text, _, _, _, _ = compiled_step()
            tracing.enable()
            on_text, jaxpr, meta, ledger, plan = compiled_step()
        finally:
            if not was_on:
                tracing.disable()
    meta["untraced_sha256"] = hashlib.sha256(off_text.encode()).hexdigest()
    return [ProgramRecord(
        "traced_step",
        description="dense SBDF2 diffusion step lowered under tracing "
                    "(must match the untraced build byte-for-byte)",
        compiled_text=on_text, jaxpr=jaxpr, meta=meta, ledger=ledger,
        plan=plan)]


@census("sharded_step_1d")
def _census_sharded_step():
    """The tests/test_collectives.py program shape: a 4-device sharded
    step must move pencils with all-to-alls and zero full-state
    gathers."""
    skip = _need_devices(4)
    if skip:
        return [ProgramRecord("sharded_step_1d", skipped=skip)]
    import jax
    from jax.sharding import Mesh
    from ...extras.bench_problems import build_tau_ivp
    from ...parallel import distribute_solver
    solver, u, x, z = build_tau_ivp()
    distribute_solver(solver, Mesh(np.array(jax.devices()[:4]), ("x",)))
    solver.step(1e-3)
    rec = _solver_record(
        "sharded_step_1d", solver,
        "SBDF2 tau-IVP step sharded over a 1-D 4-device pencil mesh",
        extra_meta={"sharded": True, "state_bytes": int(solver.X.nbytes),
                    "expected_a2a_min": 2})
    return [rec]


@census("chunked_walk_1d")
def _census_chunked_walk():
    """Overlapped chunked transpose walks (chunks=2) on a 1-D mesh: one
    all_to_all per chunk per stage, zero gathers, both directions."""
    skip = _need_devices(4)
    if skip:
        return [ProgramRecord("chunked_walk_to_grid", skipped=skip),
                ProgramRecord("chunked_walk_to_coeff", skipped=skip)]
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from ...extras.bench_problems import build_tau_ivp
    from ...parallel import DistributedPencilPipeline
    solver, u, x, z = build_tau_ivp()
    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    pipe = DistributedPencilPipeline(u.domain, mesh, "x", chunks=2)
    cdata = np.asarray(u["c"])
    c_sh = jax.device_put(cdata, NamedSharding(mesh, P("x", None)))
    records = []
    # pipeline walks have no solver and thus no plan_provenance(); the
    # chunk count IS the plan-relevant knob, declared as a minimal plan
    walk_plan = {"plan_version": 1, "transpose_chunks": 2}
    prog_g = jax.jit(pipe.to_grid)  # dedalus-lint: disable=DTL003 (one-shot census lowering)
    g = prog_g(c_sh)
    text_g, ledger_g = _compile_views(prog_g.lower(c_sh))
    records.append(ProgramRecord(
        "chunked_walk_to_grid",
        description="chunked (C=2) coeff->grid walk, 1-D pencil mesh",
        compiled_text=text_g,
        jaxpr=jax.make_jaxpr(pipe.to_grid)(c_sh),
        meta={"sharded": True, "state_bytes": int(cdata.nbytes),
              "expected_a2a_min": 2},
        ledger=ledger_g, plan=dict(walk_plan)))
    prog_c = jax.jit(pipe.to_coeff)  # dedalus-lint: disable=DTL003 (one-shot census lowering)
    text_c, ledger_c = _compile_views(prog_c.lower(g))
    records.append(ProgramRecord(
        "chunked_walk_to_coeff",
        description="chunked (C=2) grid->coeff walk, 1-D pencil mesh",
        compiled_text=text_c,
        jaxpr=jax.make_jaxpr(pipe.to_coeff)(g),
        meta={"sharded": True, "state_bytes": int(cdata.nbytes),
              "expected_a2a_min": 2},
        ledger=ledger_c, plan=dict(walk_plan)))
    return records


@census("chunked_walk_2dmesh")
def _census_chunked_walk_2d():
    """R=2 chunked walk on a 2-D (2x4) pencil mesh over a 3-D domain:
    both mesh axes' stages chunk — the walk composition the 2048x1024
    north star runs."""
    skip = _need_devices(8)
    if skip:
        return [ProgramRecord("chunked_walk_2dmesh", skipped=skip)]
    import jax
    import dedalus_tpu.public as d3
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from ...parallel import DistributedPencilPipeline
    coords = d3.CartesianCoordinates("x", "y", "z")
    dist = d3.Distributor(coords, dtype=np.float64)
    xb = d3.RealFourier(coords["x"], size=8, bounds=(0, 2 * np.pi))
    yb = d3.RealFourier(coords["y"], size=8, bounds=(0, 2 * np.pi))
    # z=16 so BOTH stages' destination blocks tile their mesh axis into
    # 2 chunks (16/4=4, 8/2=4): the declared a2a floor is 2 per stage
    zb = d3.ChebyshevT(coords["z"], size=16, bounds=(0, 1))
    f = dist.Field(name="f", bases=(xb, yb, zb))
    x, y, z = dist.local_grids(xb, yb, zb)
    f["g"] = np.sin(2 * x) * np.cos(y) * z ** 2 + np.sin(y) + 1
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("px", "py"))
    pipe = DistributedPencilPipeline(f.domain, mesh, ("px", "py"), chunks=2)
    cdata = np.asarray(f["c"])
    c_sh = jax.device_put(cdata,
                          NamedSharding(mesh, P("px", "py", None)))
    prog = jax.jit(pipe.to_grid)  # dedalus-lint: disable=DTL003 (one-shot census lowering)
    text, ledger = _compile_views(prog.lower(c_sh))
    return [ProgramRecord(
        "chunked_walk_2dmesh",
        description="chunked (C=2) coeff->grid walk, 2-D (2x4) mesh, "
                    "3-D domain",
        compiled_text=text,
        jaxpr=jax.make_jaxpr(pipe.to_grid)(c_sh),
        meta={"sharded": True, "state_bytes": int(cdata.nbytes),
              "expected_a2a_min": 4},
        ledger=ledger, plan={"plan_version": 1, "transpose_chunks": 2})]


@census("fleet_2d")
def _census_fleet_2d():
    """The 2-D batch x pencil fleet step program (members vmapped over
    batch, pencils GSPMD-auto inside the manual member shard_map): zero
    full-state gathers — the assertion this program never had — plus the
    pad-free partial-auto region."""
    skip = _need_devices(8)
    if skip:
        return [ProgramRecord("fleet_2d", skipped=skip)]
    import jax
    from jax.sharding import Mesh
    from ...extras.bench_problems import build_tau_ivp
    solver, u, x, z = build_tau_ivp()
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                ("batch", "pencil"))
    fleet = solver.ensemble(2, mesh=mesh)

    def ics(i):
        u["g"] = np.sin(np.pi * z) * (1 + 0.1 * (i + 1)
                                      * np.cos(np.pi * x / 2))

    fleet.init_members(ics)
    fleet.step_many(4, 1e-3)
    prog, args = fleet.step_program_handle()
    text, ledger = _compile_views(prog.lower(*args))
    return [ProgramRecord(
        "fleet_2d",
        description="2-member fleet step on a 2-D (2 batch x 4 pencil) "
                    "mesh",
        compiled_text=text,
        jaxpr=jax.make_jaxpr(prog)(*args),
        meta={"sharded": True, "state_bytes": int(fleet.X.nbytes),
              "expected_a2a_min": 2, "manual_auto": True},
        ledger=ledger, plan=_plan_of(solver))]


@census("ensemble_fleet_1d")
def _census_fleet_1d():
    """The plain vmapped ensemble fleet step on a 1-D member mesh: the
    serving micro-batch program shape (service/batching.py anchors on
    exactly this fleet)."""
    skip = _need_devices(2)
    if skip:
        return [ProgramRecord("ensemble_fleet_1d", skipped=skip)]
    import jax
    from jax.sharding import Mesh
    from ...extras.bench_problems import build_tau_ivp
    solver, u, x, z = build_tau_ivp()
    fleet = solver.ensemble(2, mesh=Mesh(np.array(jax.devices()[:2]),
                                         ("batch",)))

    def ics(i):
        u["g"] = np.sin(np.pi * z) * (1 + 0.1 * (i + 1)
                                      * np.cos(np.pi * x / 2))

    fleet.init_members(ics)
    fleet.step_many(4, 1e-3)
    prog, args = fleet.step_program_handle()
    text, ledger = _compile_views(prog.lower(*args))
    return [ProgramRecord(
        "ensemble_fleet_1d",
        description="2-member vmapped fleet step, 1-D member mesh",
        compiled_text=text,
        jaxpr=jax.make_jaxpr(prog)(*args),
        meta={"sharded": True, "state_bytes": int(fleet.X.nbytes)},
        ledger=ledger, plan=_plan_of(solver))]


@census("adjoint_grad")
def _census_adjoint():
    """The compiled value_and_grad program (checkpointed-backprop scan +
    custom-VJP adjoint solves): host callbacks would break the transpose
    — forbidden."""
    import jax.numpy as jnp
    from ...extras.bench_problems import build_diffusion_solver
    solver = build_diffusion_solver(48)
    div = solver.differentiable(wrt=("initial_state",),
                                loss=lambda X: jnp.sum(X * X))
    prog, args = div.grad_program_handle(4, 1e-3)
    text, ledger = _compile_views(prog.lower(*args))
    return [ProgramRecord(
        "adjoint_grad",
        description="value_and_grad over 4 SBDF2 diffusion steps "
                    "(checkpointed adjoint)",
        compiled_text=text,
        jaxpr=prog.jaxpr(*args),
        ledger=ledger, plan=_plan_of(solver))]


@census("pool_step")
def _census_pool_step():
    """A warm-pool entry's compiled step program (the serving path):
    pooled programs carry the same donation/callback contracts as
    in-process solves — a pool-only regression must fail the census, not
    surface as a served memory blowup."""
    from ...service.pool import SolverPool
    with _pinned_config("fusion", DONATE_STEP="on"):
        pool = SolverPool(size=1)
        entry, verdict, _ = pool.acquire(
            {"problem": "diffusion", "params": {"size": 32}})
        solver = entry.solver
        solver.step(1e-3)
        rec = _solver_record(
            "pool_step", solver,
            f"warm-pool diffusion entry step program (verdict {verdict})",
            extra_meta={"donated": 3})
    return [rec]


# -------------------------------------------------------------- the runner

def run_census(names=None, fast_only=False):
    """Build the census. Returns (records, timings): every registered
    (or selected) program builds exactly once; a builder needing more
    devices than the process has yields skipped records (reported, never
    silently absent). Raises KeyError on an unknown selection — a typo'd
    program name must not report a clean census."""
    selected = census_names(fast_only) if names is None else list(names)
    unknown = [n for n in selected if n not in CENSUS]
    if unknown:
        raise KeyError(f"unknown census program(s) {unknown}; "
                       f"known: {sorted(CENSUS)}")
    records = []
    timings = {}
    for name in selected:
        builder, _ = CENSUS[name]
        t0 = time.perf_counter()
        built = builder()
        wall = time.perf_counter() - t0
        timings[name] = wall
        for rec in built:
            if not rec.build_sec:
                rec.build_sec = wall / max(len(built), 1)
            records.append(rec)
    return records, timings


def ledger_rows(records):
    """One `kind: ledger` trajectory row per costed census program, in
    the benchmarks/results.jsonl vocabulary: the program's resource
    ledger plus scan depth, plan provenance, and the host/environment
    fingerprint — the read-side input of tools/perfwatch.py. Skipped or
    un-costed records yield no row (absence stays explicit in the census
    report instead)."""
    from ..envinfo import env_fingerprint
    try:
        import jax
        backend = str(jax.default_backend())
    except Exception:
        backend = None
    env = env_fingerprint()
    rows = []
    for rec in records:
        if rec.skipped or rec.ledger is None:
            continue
        row = {"kind": "ledger", "config": "progcheck_census",
               "program": rec.name, "backend": backend}
        row.update(rec.ledger)
        if rec.jaxpr is not None:
            lengths, whiles = scan_lengths(rec.jaxpr)
            row["scan_max_length"] = max(lengths, default=0)
            row["while_loops"] = whiles
        row["plan"] = rec.plan
        row["env"] = env
        rows.append(row)
    return rows


def append_ledger_rows(records, path=None):
    """Persist ledger rows alongside the perf rows. Opt-in by design:
    the census itself never writes — tests and ad-hoc runs must not
    grow the checked-in trajectory. Returns the appended rows."""
    import json
    path = pathlib.Path(path) if path \
        else PACKAGE_DIR.parent / "benchmarks" / "results.jsonl"
    rows = ledger_rows(records)
    ts = round(time.time(), 1)
    with open(path, "a") as f:
        for row in rows:
            row.setdefault("ts", ts)
            f.write(json.dumps(row) + "\n")
    return rows


def check_records(records, contracts=None):
    """Run the contract registry over census records. Returns
    (findings, suppressed, contract_timings); per-record waivers land in
    `suppressed` (counted, never hidden), skipped records are not
    checked."""
    contracts = all_contracts() if contracts is None else contracts
    findings, suppressed = [], []
    timings = {}
    for contract in contracts:
        t0 = time.perf_counter()
        for rec in records:
            if rec.skipped:
                continue
            for finding in contract.check(rec):
                if contract.id in rec.meta.get("waive", ()):
                    suppressed.append(finding)
                else:
                    findings.append(finding)
        timings[contract.id] = timings.get(contract.id, 0.0) \
            + time.perf_counter() - t0
    return findings, suppressed, timings


def run_programs(names=None, contracts=None, fast_only=False,
                 baseline_path=None, no_baseline=False, ledger_path=None):
    """The programs-tier entry point (cli --programs and
    tests/test_progcheck.py): census + contracts + baseline. Returns the
    summary dict the CLI renders:
    {programs, findings (new, as dicts), summary{total,new,baselined,
    suppressed,stale}, timings{census,contracts}}.

    `ledger_path` (cli --ledger) additionally appends one `kind: ledger`
    trajectory row per costed program there; the default call appends
    nothing."""
    if contracts is not None:
        unknown = [c for c in contracts if c not in CONTRACTS]
        if unknown:
            raise KeyError(f"unknown contract(s) {unknown}; "
                           f"known: {sorted(CONTRACTS)}")
        contracts = [CONTRACTS[c] for c in contracts]
    records, census_timings = run_census(names, fast_only=fast_only)
    findings, suppressed, contract_timings = check_records(records,
                                                           contracts)
    baseline = {} if no_baseline \
        else load_baseline(baseline_path or PROGRAMS_BASELINE)
    new, stale = apply_baseline(findings, baseline)
    ledger_appended = None
    if ledger_path is not None:
        ledger_appended = len(append_ledger_rows(records, ledger_path))
    return {
        "programs": [rec.stats() for rec in records],
        "findings": [f.to_dict() for f in new],
        "summary": {
            "total": len(findings),
            "new": len(new),
            "baselined": len(findings) - len(new),
            "suppressed": len(suppressed),
            "stale": stale,
            "checked": sum(1 for r in records if not r.skipped),
            "skipped": [r.name for r in records if r.skipped],
            **({"ledger_rows": ledger_appended}
               if ledger_appended is not None else {}),
        },
        "timings": {
            "census": {k: round(v, 3) for k, v in census_timings.items()},
            "contracts": {k: round(v, 4)
                          for k, v in contract_timings.items()},
        },
    }
