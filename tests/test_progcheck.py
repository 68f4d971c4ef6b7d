"""
Compiled-program contract checker (tools/lint/progcheck.py).

Two layers of proof:

  * the REAL census: the fast subset lowers the shipped step/fleet/grad/
    pool programs on the virtual CPU mesh and must report ZERO new
    findings against the checked-in progcheck_baseline.json — this is
    the tier-1 gate that keeps every future PR's compiled programs
    contract-checked by default;
  * SEEDED regressions: each encoded bug class (a dropped donation, a
    restored jnp.pad in a partial-auto region, a gather-degraded chunk
    stage, a triangular custom call on the fused path, a host callback
    in a step body) is reproduced as a small fixture program and must
    produce its NAMED finding — so a quiet census is evidence the
    contracts look, not that they cannot see.
"""

import hashlib
import re
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dedalus_tpu.tools.compat import shard_map
from dedalus_tpu.tools.lint import progcheck
from dedalus_tpu.tools.lint.cli import main as lint_main
from dedalus_tpu.tools.lint.framework import apply_baseline, make_baseline
from dedalus_tpu.tools.lint.progcheck import (CONTRACTS, ProgramRecord,
                                              check_records,
                                              collective_counts,
                                              donated_alias_count,
                                              gather_buffers,
                                              pads_in_auto_regions,
                                              record_from_jit, run_programs)

pytestmark = pytest.mark.progcheck

N_DEV = len(jax.devices())
needs_devices = pytest.mark.skipif(N_DEV < 4, reason="needs >= 4 devices")
needs_8 = pytest.mark.skipif(N_DEV < 8, reason="needs >= 8 devices")

# the tier-1 subset: every contract exercised on at least one REAL
# program, the expensive banded-RB builds left to the full CLI census
# (tau_step_ascan is the fast DTP106 anchor: a small banded build whose
# lowered step must carry no sequential substitution scan; traced_step
# is the DTP107 anchor: the same step lowered with tracing on must hash
# to the untraced build)
FAST_SUBSET = ["diffusion_step", "sharded_step_1d", "chunked_walk_1d",
               "fleet_2d", "adjoint_grad", "pool_step", "tau_step_ascan",
               "traced_step"]


def _rules_fired(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------- the real census

@pytest.fixture(scope="module")
def fast_report():
    """One fast-subset census per module: the expensive part of every
    real-program assertion below."""
    return run_programs(names=FAST_SUBSET)


@needs_8
def test_census_head_is_clean(fast_report):
    """The acceptance gate: the shipped programs carry zero new contract
    findings and the checked-in baseline is empty and fresh."""
    summary = fast_report["summary"]
    assert summary["new"] == 0, fast_report["findings"]
    assert summary["stale"] == []
    assert summary["skipped"] == []
    # the baseline is empty on a healthy tree — true positives get fixed,
    # not grandfathered
    assert summary["baselined"] == 0


@needs_8
def test_census_breadth(fast_report):
    """The subset really lowers the distinct program shapes the
    contracts claim to cover: a sharded step, a chunked walk (both
    directions), a 2-D batch x pencil fleet, an adjoint grad program and
    a pool-served entry."""
    rows = {row["program"]: row for row in fast_report["programs"]}
    assert set(rows) == {"diffusion_step", "sharded_step_1d",
                         "chunked_walk_to_grid", "chunked_walk_to_coeff",
                         "fleet_2d", "adjoint_grad", "pool_step",
                         "tau_step_ascan", "traced_step"}
    # collective placement facts the weak-scaling/fusion claims rest on
    assert rows["sharded_step_1d"]["collectives"]["all-to-all"] >= 2
    assert rows["sharded_step_1d"]["collectives"]["all-gather"] == 0
    assert rows["fleet_2d"]["collectives"]["all-gather"] == 0
    assert rows["fleet_2d"]["pads_in_auto_regions"] == 0
    assert rows["chunked_walk_to_grid"]["collectives"]["all-to-all"] >= 2
    # donation honored on the donating programs
    assert rows["diffusion_step"]["donated_aliases"] >= 3
    assert rows["pool_step"]["donated_aliases"] >= 3
    # the depth contract's fast anchor: the associative-scan step's
    # longest surviving scan sits under its declared log-depth bound
    ascan = rows["tau_step_ascan"]
    assert ascan["fused_solve"] is True
    assert ascan["while_loops"] == 0
    assert max(ascan["scan_lengths"], default=0) <= ascan["max_scan_length"]
    # the tracing-inert anchor: the census carried the untraced build's
    # hash, and head-clean above means the traced build matched it
    traced = rows["traced_step"]
    assert re.fullmatch(r"[0-9a-f]{64}", traced["untraced_sha256"])
    # per-contract timings recorded for every registered contract
    assert set(fast_report["timings"]["contracts"]) == set(CONTRACTS)


@needs_8
def test_full_census_names_cover_required_shapes():
    """The FULL census registry (the `lint --programs` default) includes
    the fused and unfused RB banded steps on top of the fast subset."""
    names = progcheck.census_names()
    for required in ("rb_step_fused", "rb_step_unfused", "diffusion_step",
                     "sharded_step_1d", "chunked_walk_1d",
                     "chunked_walk_2dmesh", "fleet_2d",
                     "ensemble_fleet_1d", "adjoint_grad", "pool_step",
                     "tau_step_ascan", "rb_step_spike", "rb_step_ladder",
                     "traced_step"):
        assert required in names
    fast = progcheck.census_names(fast_only=True)
    assert "rb_step_fused" not in fast and "rb_step_unfused" not in fast
    assert "rb_step_spike" not in fast and "rb_step_ladder" not in fast
    assert "tau_step_ascan" in fast


# ------------------------------------------------ seeded regressions

def test_seeded_dropped_donation():
    """A program that declares donated buffers but compiles without the
    aliases (the dropped-donation memory regression) produces a named
    DTP104 finding; the same program WITH donation passes."""
    args = (jnp.ones((8, 8)), jnp.ones((8, 8)))

    def body(a, b):
        return a + 1.0, b * 2.0

    dropped = record_from_jit("seed_drop_donation", body, args,
                              meta={"donated": 2})
    findings, _, _ = check_records([dropped])
    assert _rules_fired(findings) == ["DTP104"]
    assert "donation was dropped" in findings[0].message
    honored = record_from_jit("seed_honored_donation", body, args,
                              meta={"donated": 2}, donate_argnums=(0, 1))
    assert donated_alias_count(honored.compiled_text) == 2
    findings, _, _ = check_records([honored])
    assert findings == []


def test_seeded_tracing_divergence():
    """A program whose tracing-enabled build hashes differently from its
    declared untraced build (instrumentation leaked into the lowered
    computation) produces a named DTP107 finding; a matching hash — and
    a record with no declared hash — pass."""
    args = (jnp.ones((8, 8)),)

    def body(a):
        return a * 2.0

    rec = record_from_jit("seed_traced_match", body, args)
    rec.meta["untraced_sha256"] = hashlib.sha256(
        rec.compiled_text.encode()).hexdigest()
    findings, _, _ = check_records([rec])
    assert findings == []

    diverged = record_from_jit("seed_traced_diverged", body, args)
    diverged.meta["untraced_sha256"] = hashlib.sha256(
        (diverged.compiled_text + "x").encode()).hexdigest()
    findings, _, _ = check_records([diverged])
    assert _rules_fired(findings) == ["DTP107"]
    assert "instrumentation has leaked" in findings[0].message

    undeclared = record_from_jit("seed_traced_undeclared", body, args)
    findings, _, _ = check_records([undeclared])
    assert findings == []


@needs_devices
def test_seeded_pad_in_auto_region():
    """jnp.pad restored inside a PARTIAL-AUTO shard_map region (the
    jaxlib SPMD-partitioner crash class) produces DTP105; the identical
    pad inside a FULLY MANUAL region is exempt (explicitly partitioned),
    and the zeropad lowering passes everywhere."""
    from dedalus_tpu.tools.array import zeropad
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("a", "b"))
    x = jnp.ones((8, 8))

    def padded(block):
        return jnp.pad(block, ((0, 0), (1, 1)))[:, 1:-1] * 2.0

    def zeropadded(block):
        return zeropad(block, ((0, 0), (1, 1)))[:, 1:-1] * 2.0

    def wrap(body, auto):
        kw = ({"check_vma": False, "axis_names": frozenset({"a"})}
              if auto else {})
        return partial(shard_map, mesh=mesh, in_specs=P("a"),
                       out_specs=P("a"), **kw)(body)

    # compile=False: compiling this program ABORTS the process inside
    # the XLA partitioner (the crash is a CHECK failure, not a raisable
    # error) — the contract's value is precisely that it catches the pad
    # at the jaxpr tier, before any compile
    bad = record_from_jit("seed_pad_auto", wrap(padded, auto=True), (x,),
                          compile=False)
    assert pads_in_auto_regions(bad.jaxpr) == 1
    findings, _, _ = check_records([bad])
    assert _rules_fired(findings) == ["DTP105"]
    assert "partial-auto" in findings[0].message
    manual = record_from_jit("seed_pad_manual", wrap(padded, auto=False),
                             (x,))
    fixed = record_from_jit("seed_zeropad_auto", wrap(zeropadded, auto=True),
                            (x,))
    findings, _, _ = check_records([manual, fixed])
    assert findings == []


@needs_devices
def test_seeded_gather_degraded_stage():
    """A stage that gathers the full state instead of exchanging
    all-to-all (the GSPMD fallback) fails BOTH ways: the state-sized
    gather (DTP101) and the missing declared all-to-all (DTP103)."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    x = jax.device_put(jnp.arange(64.0).reshape(16, 4),
                       NamedSharding(mesh, P("x", None)))

    @partial(shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    def degraded(block):
        full = jax.lax.all_gather(block, "x", tiled=True)
        return full[:block.shape[0]] * 2.0

    meta = {"sharded": True, "state_bytes": int(x.nbytes),
            "expected_a2a_min": 1}
    rec = record_from_jit("seed_gather_degraded", degraded, (x,), meta=meta)
    assert gather_buffers(rec.compiled_text)
    findings, _, _ = check_records([rec])
    assert _rules_fired(findings) == ["DTP101", "DTP103"]
    by_rule = {f.rule: f for f in findings}
    assert "full-state all-gather" in by_rule["DTP101"].message
    assert "degraded to a gather" in by_rule["DTP103"].message
    # the size-aware bound: the SAME gather against a much larger
    # declared state is a small bookkeeping gather, not a violation
    small = record_from_jit(
        "seed_small_gather", degraded, (x,),
        meta={"sharded": True, "state_bytes": int(x.nbytes) * 100})
    findings, _, _ = check_records([small])
    assert findings == []


def test_seeded_triangular_on_fused_path():
    """A triangular/pivot solve inside a program declared fused_solve
    (the precomposed-GEMM substitution) produces DTP102; the same
    program NOT declared fused (the legacy path) is legal."""
    A = jnp.eye(6) + jnp.tril(jnp.ones((6, 6))) * 0.1
    b = jnp.ones(6)

    def solve(A, b):
        return jax.scipy.linalg.solve_triangular(A, b, lower=True)

    fused = record_from_jit("seed_fused_triangular", solve, (A, b),
                            meta={"fused_solve": True})
    findings, _, _ = check_records([fused])
    assert _rules_fired(findings) == ["DTP102"]
    assert "triangular" in findings[0].message or \
        "triangular_solve" in findings[0].snippet
    legacy = record_from_jit("seed_legacy_triangular", solve, (A, b))
    findings, _, _ = check_records([legacy])
    assert findings == []


def test_seeded_host_callback_in_step_body():
    """A host callback compiled into any census program body produces
    DTP102 regardless of fusion flags (no transpose rule, serializes
    dispatch)."""
    from jax.experimental import io_callback

    def body(x):
        io_callback(lambda v: None, None, x[0])
        return x * 2.0

    rec = record_from_jit("seed_callback", body, (jnp.ones(4),))
    findings, _, _ = check_records([rec])
    assert "DTP102" in _rules_fired(findings)
    assert any("callback" in f.message for f in findings)


def test_seeded_sequential_scan_regression():
    """A lax.scan longer than the declared substitution depth bound
    produces DTP106 (the depth claim made machine-checkable); the same
    program without the declaration is legal, and a while loop inside a
    depth-bounded program is flagged as unprovable."""

    def seq_sweep(ops, x):
        def body(c, op):
            return op @ c, c
        out, _ = jax.lax.scan(body, x, ops)
        return out

    ops = jnp.stack([jnp.eye(4)] * 64)
    x = jnp.ones(4)
    rec = record_from_jit("seed_seq_scan", seq_sweep, (ops, x),
                          meta={"max_scan_length": 5})
    findings, _, _ = check_records([rec])
    assert _rules_fired(findings) == ["DTP106"]
    assert "64" in findings[0].message
    undeclared = record_from_jit("seed_seq_scan_free", seq_sweep, (ops, x))
    findings, _, _ = check_records([undeclared])
    assert findings == []
    # an in-bound refinement loop passes
    small = record_from_jit(
        "seed_small_scan", seq_sweep, (jnp.stack([jnp.eye(4)] * 3), x),
        meta={"max_scan_length": 5})
    findings, _, _ = check_records([small])
    assert findings == []

    def while_sweep(x):
        return jax.lax.while_loop(lambda v: jnp.sum(v) < 1e3,
                                  lambda v: v * 2.0, x)

    wrec = record_from_jit("seed_while", while_sweep, (jnp.ones(4),),
                           meta={"max_scan_length": 5})
    findings, _, _ = check_records([wrec])
    assert _rules_fired(findings) == ["DTP106"]
    assert "while" in findings[0].message


# -------------------------------------- baseline/waiver discipline

def test_program_findings_baseline_roundtrip():
    """Program findings grandfather exactly like AST findings: stable
    pseudo-path keys, counts absorbed, staleness when fixed."""
    rec = record_from_jit("seed_baseline", lambda a: a + 1.0,
                          (jnp.ones(4),), meta={"donated": 1})
    findings, _, _ = check_records([rec])
    assert _rules_fired(findings) == ["DTP104"]
    key = findings[0].key()
    assert key[1] == "__programs__/seed_baseline.hlo"
    baseline = {k: 1 for k in {f.key() for f in findings}}
    new, stale = apply_baseline(findings, baseline)
    assert new == [] and stale == []
    # fixing the program leaves the entry stale (the baseline shrinks)
    fixed = record_from_jit("seed_baseline", lambda a: a + 1.0,
                            (jnp.ones(4),), meta={"donated": 1},
                            donate_argnums=(0,))
    findings, _, _ = check_records([fixed])
    new, stale = apply_baseline(findings, baseline)
    assert new == [] and len(stale) == 1
    assert stale[0]["rule"] == "DTP104" and stale[0]["missing"] == 1
    # make_baseline round-trips the same keys
    data = make_baseline([])
    assert data["entries"] == []


def test_program_waiver_counts_as_suppressed():
    """A census entry can waive a contract for one program; the finding
    is counted as suppressed, never silently dropped."""
    rec = record_from_jit("seed_waived", lambda a: a + 1.0,
                          (jnp.ones(4),),
                          meta={"donated": 1, "waive": {"DTP104"}})
    findings, suppressed, _ = check_records([rec])
    assert findings == []
    assert _rules_fired(suppressed) == ["DTP104"]


def test_skipped_records_are_reported_not_checked():
    rec = ProgramRecord("needs_more_devices", skipped="needs >= 64 devices")
    findings, _, _ = check_records([rec])
    assert findings == []
    summary = {"skipped": rec.skipped}
    assert "64" in summary["skipped"]


def test_unknown_selection_raises():
    with pytest.raises(KeyError, match="unknown census program"):
        progcheck.run_census(["nope"])
    with pytest.raises(KeyError, match="unknown contract"):
        run_programs(names=[], contracts=["DTPXXX"])


# ------------------------------------------------------------ CLI wiring

@needs_8
def test_cli_programs_json_roundtrip(capsys):
    """`lint --programs --json` (the standalone CI invocation) renders
    the census + per-contract timings and exits 0 on the healthy tree."""
    import json
    rc = lint_main(["--programs", "--select", "diffusion_step",
                    "--contracts", "DTP102,DTP104", "--format", "json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert report["summary"]["new"] == 0
    assert report["programs"][0]["program"] == "diffusion_step"
    assert report["programs"][0]["donated_aliases"] >= 3
    assert set(report["timings"]["contracts"]) == {"DTP102", "DTP104"}
    assert report["timings"]["census"]["diffusion_step"] > 0


def test_cli_programs_exits_nonzero_on_new_finding(capsys, monkeypatch):
    """A seeded census regression drives the CLI to rc 1 with the named
    finding — the property standalone CI relies on."""
    def bad_builder():
        return [record_from_jit(
            "seed_cli_bad", lambda a: a + 1.0, (jnp.ones(4),),
            meta={"donated": 1})]

    monkeypatch.setitem(progcheck.CENSUS, "seed_cli_bad",
                        (bad_builder, True))
    rc = lint_main(["--programs", "--select", "seed_cli_bad"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "DTP104" in out and "1 new" in out


def test_cli_programs_update_baseline_refuses_subset(capsys, tmp_path):
    """Regenerating the PROGRAMS baseline from a census subset would
    drop entries outside the selection — same refusal discipline as the
    AST tier; a scoped --baseline FILE is the sanctioned escape."""
    before = progcheck.PROGRAMS_BASELINE.read_text()
    rc = lint_main(["--programs", "--select", "diffusion_step",
                    "--update-baseline"])
    assert rc == 2
    assert "refusing" in capsys.readouterr().err
    assert progcheck.PROGRAMS_BASELINE.read_text() == before


def test_cli_programs_rejects_paths(capsys):
    rc = lint_main(["--programs", "dedalus_tpu/"])
    assert rc == 2
    assert "--programs" in capsys.readouterr().err


# ----------------------------------------------------- analysis helpers

def test_collective_counts_parser():
    text = """
  %a = f64[4,8]{1,0} all-to-all(f64[4,8]{1,0} %p), replica_groups={}
  %b = f64[16,8]{1,0} all-gather(f64[4,8]{1,0} %p), dimensions={0}
  %c = (f64[16,8]{1,0}, f64[4]{0}) all-gather-start(f64[4,8]{1,0} %p)
  %d = f64[4,8]{1,0} all-reduce(f64[4,8]{1,0} %p)
"""
    counts = collective_counts(text)
    assert counts["all-to-all"] == 1
    assert counts["all-gather"] == 2
    assert counts["all-reduce"] == 1
    sizes = gather_buffers(text)
    assert ("f64", "16,8", 16 * 8 * 8) in sizes


def test_donated_alias_count_parser():
    head = ("HloModule jit_f, is_scheduled=true, input_output_alias={ "
            "{0}: (5, {}, may-alias), {1}: (6, {}, may-alias), "
            "{2}: (7, {}, may-alias) }, entry_computation_layout={...}\n"
            "ENTRY %main ...")
    assert donated_alias_count(head) == 3
    assert donated_alias_count("HloModule jit_f, is_scheduled=true\n") == 0


# ------------------------------------------------------- resource ledger

class _FakeMem:
    argument_size_in_bytes = 100
    output_size_in_bytes = 40
    temp_size_in_bytes = 60
    generated_code_size_in_bytes = 7
    alias_size_in_bytes = 30


class _FakeCompiled:
    def cost_analysis(self):
        return {"flops": 123.0, "transcendentals": 4.0,
                "bytes accessed": 456.0}

    def memory_analysis(self):
        return _FakeMem()


def test_program_ledger_full():
    text = ("  %a = f32[4]{0} add(f32[4]{0} %p, f32[4]{0} %p)\n"
            "  ROOT %b = f32[4]{0} multiply(%a, %a)\n")
    led = progcheck.program_ledger(_FakeCompiled(), hlo_text=text)
    assert led["ledger_version"] == 1
    assert led["flops"] == 123
    assert led["transcendentals"] == 4
    assert led["bytes_accessed"] == 456
    assert led["argument_bytes"] == 100
    assert led["peak_bytes"] == 100 + 40 + 60 - 30   # alias-corrected
    assert led["hlo_instructions"] == 2


def test_program_ledger_guarded_fallbacks():
    """A backend where the analyses are absent or raise yields nulls for
    their fields and never an exception — the census stays green."""
    class Raising:
        def cost_analysis(self):
            raise NotImplementedError("no cost analysis here")

        def memory_analysis(self):
            raise RuntimeError("nor memory analysis")

    led = progcheck.program_ledger(Raising())
    assert led["ledger_version"] == 1
    assert all(led[f] is None for f in progcheck.LEDGER_FIELDS)

    class Missing:
        pass                      # neither method exists at all

    led = progcheck.program_ledger(Missing(), hlo_text="%r = f32[] x()")
    assert led["flops"] is None and led["peak_bytes"] is None
    assert led["hlo_instructions"] == 1

    class OldStyle:               # list-of-dicts cost_analysis (old jax)
        def cost_analysis(self):
            return [{"flops": 9.0}, {"flops": 1.0}]

        def memory_analysis(self):
            raise RuntimeError("unavailable")

    led = progcheck.program_ledger(OldStyle())
    assert led["flops"] == 9                   # main computation first
    assert led["bytes_accessed"] is None
    assert led["argument_bytes"] is None


def test_record_from_jit_carries_ledger():
    rec = record_from_jit("seed_ledgered",
                          lambda a: jnp.sin(a) * 2.0, (jnp.ones(64),))
    assert rec.ledger is not None
    assert rec.ledger["ledger_version"] == 1
    assert rec.ledger["hlo_instructions"] > 0
    assert rec.stats()["ledger"] == rec.ledger
    # jaxpr-only records (the DTP105 tier) carry no ledger — and report
    # none rather than zeros
    uncompiled = record_from_jit("seed_uncompiled", lambda a: a + 1.0,
                                 (jnp.ones(4),), compile=False)
    assert uncompiled.ledger is None
    assert "ledger" not in uncompiled.stats()


def test_ledger_rows_shape_and_scan_depth():
    def scanned(x):
        return jax.lax.scan(lambda c, _: (c + 1.0, None), x, None,
                            length=17)[0]

    rec = record_from_jit("seed_ledger_row", scanned, (jnp.ones(8),))
    skipped = ProgramRecord("too_big", skipped="needs >= 64 devices")
    rows = progcheck.ledger_rows([rec, skipped])
    assert len(rows) == 1                       # skipped yields no row
    row = rows[0]
    assert row["kind"] == "ledger"
    assert row["config"] == "progcheck_census"
    assert row["program"] == "seed_ledger_row"
    assert row["scan_max_length"] == 17
    assert row["while_loops"] == 0
    assert row["plan"] is None                  # fixture has no solver
    assert row["env"]["env_version"] == 1
    assert row["env"]["python"]                 # fingerprint is stamped
    assert row["hlo_instructions"] > 0


def test_append_ledger_rows_appends(tmp_path):
    import json
    rec = record_from_jit("seed_ledger_append", lambda a: a * 2.0,
                          (jnp.ones(8),))
    sink = tmp_path / "results.jsonl"
    rows = progcheck.append_ledger_rows([rec], sink)
    assert len(rows) == 1
    lines = sink.read_text().splitlines()
    assert len(lines) == 1
    row = json.loads(lines[0])
    assert row["program"] == "seed_ledger_append"
    assert row["ts"] > 0
    progcheck.append_ledger_rows([rec], sink)   # append, never truncate
    assert len(sink.read_text().splitlines()) == 2


def test_cli_programs_ledger_flag(capsys, monkeypatch, tmp_path):
    """`lint --programs --ledger PATH` appends trajectory rows and says
    so; without the flag the census writes nothing."""
    import json

    def builder():
        return [record_from_jit("seed_ledger_cli", lambda a: a * 2.0,
                                (jnp.ones(8),))]

    monkeypatch.setitem(progcheck.CENSUS, "seed_ledger_cli",
                        (builder, True))
    sink = tmp_path / "results.jsonl"
    rc = lint_main(["--programs", "--select", "seed_ledger_cli",
                    "--ledger", str(sink)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "ledger: 1 trajectory row(s) appended" in out
    row = json.loads(sink.read_text().splitlines()[0])
    assert row["kind"] == "ledger"
    assert row["program"] == "seed_ledger_cli"
    rc = lint_main(["--programs", "--select", "seed_ledger_cli"])
    capsys.readouterr()
    assert rc == 0
    assert len(sink.read_text().splitlines()) == 1   # opt-in: no growth
