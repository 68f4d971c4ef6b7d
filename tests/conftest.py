"""
Test configuration: force the CPU backend (the suite never claims a chip;
the TPU compiler is reached only through tests/test_chip_compile.py's
described topology) and expose a virtual 8-device mesh for sharding tests.
"""

import os

# Must be set before the backend initializes.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Hermetic assembly cache: the persistent matrix cache stays EXERCISED
# (its own tests depend on it; ambient solver builds hit/store too) but
# against a per-session temporary directory, so a stale ~/.cache entry
# written by a different checkout can never leak into test results.
# An explicit DEDALUS_TPU_ASSEMBLY_CACHE (e.g. the cross-process reuse
# test's subprocess env) still wins.
if "DEDALUS_TPU_ASSEMBLY_CACHE" not in os.environ:
    import atexit
    import shutil
    import tempfile

    _assembly_cache_tmp = tempfile.mkdtemp(
        prefix="dedalus_test_assembly_cache_")
    os.environ["DEDALUS_TPU_ASSEMBLY_CACHE"] = _assembly_cache_tmp
    atexit.register(shutil.rmtree, _assembly_cache_tmp, ignore_errors=True)

import pathlib  # noqa: E402
import signal  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# ------------------------------------------------- service test watchdog
#
# Hard per-test timeout for the `service`, `chaos` and `ensemble`
# markers: a daemon subprocess (or an in-process daemon thread, or a
# wedged fleet reshard/collective) that hangs must not eat the tier-1
# budget silently — the SIGALRM handler kills every registered stray
# daemon, appends their captured logs to the failure message, and fails
# THIS test instead of stalling the whole sweep. Tests that spawn
# daemon subprocesses register them (with their log path) via
# `register_daemon`, imported from this conftest.

SERVICE_TEST_TIMEOUT_SEC = 180.0

_live_daemons = []   # [(Popen, log_path or None)]


def register_daemon(proc, log_path=None):
    """Track a daemon subprocess so the per-test watchdog can kill it
    and surface its log if the test hangs. Append-only: the watchdog
    snapshots a registry index when each test starts, so entries must
    not shift mid-test (pruning happens when the watchdog arms)."""
    _live_daemons.append((proc, str(log_path) if log_path else None))


def _kill_stray_daemons(since=0):
    """Kill still-running daemons registered at-or-after index `since`
    (the hanging test's own spawns); OLDER live daemons — e.g. a healthy
    module-scoped shared fixture other tests still need — are reported
    but left running. Returns log tails / notes."""
    tails = []
    for i, (proc, log_path) in enumerate(list(_live_daemons)):
        if proc.poll() is not None:
            continue
        if i < since:
            tails.append(f"pre-existing daemon pid {proc.pid} left "
                         "running (shared fixture?)")
            continue
        proc.kill()
        tails.append(f"killed stray daemon pid {proc.pid}")
        if log_path:
            try:
                text = pathlib.Path(log_path).read_text()[-2000:]
                tails.append(f"--- {log_path} (tail) ---\n{text}")
            except OSError:
                pass
    del _live_daemons[since:]
    return tails


@pytest.fixture(autouse=True)
def _service_test_watchdog(request):
    """Per-test hard watchdog for service/chaos/ensemble-marked tests
    (SIGALRM; main thread only — pytest runs tests there). On expiry:
    stray daemons are killed, their logs attached, and the test fails
    with a timeout instead of wedging tier-1. The ensemble marker rides
    the same guard because a hung fleet reshard (a collective waiting on
    a device that will never answer) stalls exactly like a hung
    daemon."""
    marked = (request.node.get_closest_marker("service") is not None
              or request.node.get_closest_marker("chaos") is not None
              or request.node.get_closest_marker("ensemble") is not None
              or request.node.get_closest_marker("batching") is not None
              or request.node.get_closest_marker("fusion") is not None
              or request.node.get_closest_marker("solvecomp") is not None
              or request.node.get_closest_marker("distributed") is not None
              or request.node.get_closest_marker("progcheck") is not None
              or request.node.get_closest_marker("threadcheck") is not None)
    if not marked or threading.current_thread() is not threading.main_thread():
        yield
        return
    timeout = SERVICE_TEST_TIMEOUT_SEC
    # drop exited entries (safe here: no test is mid-flight), then mark:
    # only daemons registered DURING this test are killed on expiry — a
    # healthy shared module fixture must survive one slow neighbor
    _live_daemons[:] = [(p, lg) for p, lg in _live_daemons
                        if p.poll() is None]
    registry_mark = len(_live_daemons)

    def on_alarm(signum, frame):
        tails = _kill_stray_daemons(since=registry_mark)
        pytest.fail(
            f"service/chaos test exceeded the {timeout:.0f}s hard "
            "watchdog (tests/conftest.py); "
            + ("; ".join(tails) if tails else "no stray daemons found"),
            pytrace=False)

    try:
        previous = signal.signal(signal.SIGALRM, on_alarm)
    except (ValueError, OSError):   # non-main thread / no SIGALRM
        yield
        return
    signal.setitimer(signal.ITIMER_REAL, timeout)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


def pytest_configure(config):
    # chaos: fault-injection tests (tools/chaos.py driving the resilient
    # loop's recovery branches). Registered here as well as in
    # pyproject.toml so the marker exists even under a bare pytest
    # invocation with a stripped ini; chaos tests are tier-1 (fast, CPU)
    # and run by default — they are the proof the recovery paths work.
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection tests of the resilient "
        "solve loop (tools/resilience.py + tools/chaos.py)")
    # service: warm-pool solver daemon tests (dedalus_tpu/service/ +
    # tests/test_service.py), including live-daemon subprocesses over a
    # local socket. Tier-1 by default (fast, CPU) — the serving path
    # that is not exercised does not exist.
    config.addinivalue_line(
        "markers",
        "service: warm-pool solver service tests (dedalus_tpu/service/); "
        "tier-1 by default")
    # ensemble: fleet execution tests (core/ensemble.py), including
    # device-loss resharding. Tier-1 by default; covered by the same
    # hard watchdog as service/chaos so a hung reshard cannot eat the
    # tier-1 budget.
    config.addinivalue_line(
        "markers",
        "ensemble: fleet execution tests (core/ensemble.py: vmapped/"
        "sharded stepping, device-loss resharding); tier-1 by default")
    # batching: continuous micro-batch serving tests (service/
    # batching.py), covered by the same hard watchdog — a wedged batch
    # boundary stalls exactly like a hung daemon.
    config.addinivalue_line(
        "markers",
        "batching: continuous-batching service tests (service/"
        "batching.py: micro-batch dispatch, member fault isolation); "
        "tier-1 by default")
    # fusion: fused spectral step tests (core/fusedstep.py +
    # libraries/pencilops.py fused paths). Tier-1 by default; rides the
    # same hard watchdog — a wedged fused-vs-unfused fleet comparison
    # must not eat the tier-1 budget silently.
    config.addinivalue_line(
        "markers",
        "fusion: fused spectral step tests (core/fusedstep.py: "
        "precomposed solve/matvec/transform fusion, donation); "
        "tier-1 by default")
    # distributed: overlapped chunked transpose pipeline + 2-D
    # batch x pencil mesh composition tests. Tier-1 by default; rides
    # the same hard watchdog — a wedged collective on the virtual mesh
    # stalls exactly like a hung daemon.
    config.addinivalue_line(
        "markers",
        "distributed: overlapped distributed transpose pipeline + 2-D "
        "batch x pencil mesh tests (parallel/transposes.py, "
        "core/ensemble.py); tier-1 by default")
    # progcheck: compiled-program contract census tests (tools/lint/
    # progcheck.py). Tier-1 by default; rides the same hard watchdog —
    # a wedged census build (a hung collective on the virtual mesh)
    # stalls exactly like a hung daemon.
    config.addinivalue_line(
        "markers",
        "progcheck: compiled-program contract checker tests (tools/"
        "lint/progcheck.py: census + DTP contracts); tier-1 by default")
    # solvecomp: restructured-substitution + precision-ladder tests
    # (libraries/solvecomp.py + the pencilops/matsolvers wiring). Tier-1
    # by default; rides the same hard watchdog — a wedged banded build
    # or a hung fleet comparison stalls exactly like a hung daemon.
    config.addinivalue_line(
        "markers",
        "solvecomp: solve-composition + precision-ladder tests "
        "(libraries/solvecomp.py: associative-scan/SPIKE substitution, "
        "mixed-precision refinement); tier-1 by default")
    # threadcheck: thread-safety tier tests (tools/lint/threadcheck.py).
    # Tier-1 by default; rides the same hard watchdog — the sanitizer
    # cross-validation test drives a live in-process service worker, and
    # a wedged one stalls exactly like a hung daemon.
    config.addinivalue_line(
        "markers",
        "threadcheck: thread-safety tier tests (tools/lint/"
        "threadcheck.py: DTC rules, lock-order graph, runtime "
        "sanitizer); tier-1 by default")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _leak_sentinel(request):
    """Opt-in tracer-leak sentinel: tests marked `leak_check` run under
    jax.checking_leaks(), so a jitted path that captures tracers in
    module/global state (the classic lifted_jit-registry hazard class)
    fails the marked test instead of surfacing as a cryptic error in some
    later trace. Opt-in because the check globally disables trace caching
    (every call retraces) — too slow for the whole suite."""
    if request.node.get_closest_marker("leak_check") is None:
        yield
        return
    with jax.checking_leaks():
        yield
