"""
Service-level fault tolerance (dedalus_tpu/service/faults.py + the
server wiring): every degradation path — load shedding, deadlines (in
queue and mid-run with a checkpoint), the hung-dispatch watchdog,
circuit-breaker open/half-open/close, client drops, idempotent replay,
the memory watermark, slow-loris/torn-frame protocol abuse, SIGKILL'd
clients, and rolling daemon restarts — driven deterministically by the
chaos harness (tools/chaos.py service faults), with the daemon
surviving each fault and answering a subsequent healthy request
bit-identically to a direct in-process solve. Tier-1: the degradation
branch that is not exercised does not exist.

Budget discipline: most tests share ONE in-process daemon
(serve_forever on a thread, real sockets, real reader/worker/watchdog
threads — no subprocess JAX import tax, and sequential faults against
one long-lived daemon is exactly the production claim being tested);
counter assertions are deltas. Tests that need incompatible knobs
(watchdog cadence, abort-on-drop, memory watermark) spin their own
service; the rolling-restart test uses real daemon subprocesses
(registered with the conftest watchdog).
"""

import contextlib
import io
import json
import os
import pathlib
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from dedalus_tpu.service import faults, protocol
from dedalus_tpu.service.client import ServiceClient
from dedalus_tpu.service.server import SolverService
from dedalus_tpu.service.protocol import ServiceError
from dedalus_tpu.tools import chaos as chaos_mod
from dedalus_tpu.tools import resilience as res_mod

REPO = pathlib.Path(__file__).parent.parent

pytestmark = [pytest.mark.service, pytest.mark.chaos]

SIZE = 32
DIFF = {"problem": "diffusion", "params": {"size": SIZE}}
DT = 1e-3
STEPS = 10


def _ics():
    x = np.linspace(0, 2 * np.pi, SIZE, endpoint=False)
    return {"u": ("g", np.sin(3 * x)), "a": ("g", 0.2 * np.cos(x))}


_reference = {}


def direct_reference():
    """The direct in-process solve every healthy post-fault request is
    compared against, computed once per session."""
    if not _reference:
        solver = protocol.resolve_builder(DIFF)()
        SolverService._install_ics(solver, _ics())
        for _ in range(STEPS):
            solver.step(DT)
        _reference["u"] = np.asarray(solver.state[0].coeff_data()).copy()
    return _reference["u"]


@contextlib.contextmanager
def local_service(prewarm=False, hold_watchdog=False, **kw):
    """In-process daemon: serve_forever on a thread with real sockets,
    reader threads, executor, and watchdog. `prewarm=True` builds the
    DIFF pool entry BEFORE the watchdog starts, so a small watchdog_sec
    can be tested without the build tripping it. `hold_watchdog=True`
    leaves the watchdog's thread for the test to start
    (`svc.start_watchdog()`), once whatever it must not judge is over."""
    svc = SolverService(port=0, **kw)
    if hold_watchdog:
        svc.start_watchdog = svc._watchdog.start
        svc._watchdog.start = lambda: None
    if prewarm:
        # build AND compile before the watchdog arms: the first step of
        # a fresh solver pays the step-program compile, which a tight
        # test watchdog_sec would (correctly!) flag as no-progress
        entry, _, _ = svc.pool.acquire(DIFF)
        entry.solver.step(DT)
        # the next acquire() resets the entry to its just-built state
    thread = threading.Thread(target=svc.serve_forever,
                              kwargs={"ready_stream": io.StringIO()},
                              daemon=True)
    thread.start()
    deadline = time.monotonic() + 30
    while svc.started_ts is None:
        if time.monotonic() > deadline:
            raise RuntimeError("in-process daemon did not come up")
        time.sleep(0.01)
    try:
        yield svc
    finally:
        svc.request_drain("test teardown")
        thread.join(timeout=60)
        assert not thread.is_alive(), "in-process daemon failed to drain"


@pytest.fixture(scope="module")
def daemon(tmp_path_factory):
    """The shared long-lived daemon most fault tests aim at: faults are
    delivered sequentially against ONE process — exactly the survival
    claim under test. Knobs chosen so every sharing test works:
    queue_depth=1 (storm shedding; tests are otherwise sequential),
    idle_timeout=0.5 (slow-loris bound), a tight breaker, a telemetry
    sink, and the default complete-on-client-drop (replay needs the
    orphaned run to finish)."""
    sink = str(tmp_path_factory.mktemp("service_faults") / "served.jsonl")
    with local_service(prewarm=True, queue_depth=1, idle_timeout=0.5,
                       breaker_failures=2, breaker_cooloff=0.5,
                       sink=sink) as svc:
        svc.sink_path = sink
        yield svc


def assert_healthy(svc, tag):
    """The acceptance bar after every fault: the daemon answers a fresh
    healthy request bit-identically to a direct in-process solve."""
    client = ServiceClient(port=svc.port, timeout=120)
    result = client.run(DIFF, ics=_ics(), dt=DT, stop_iteration=STEPS)
    layout, u = result.fields["u"]
    assert layout == "c"
    assert np.array_equal(u, direct_reference()), \
        f"post-{tag} served result differs from the direct solve"
    assert result.result["stopped_by"] == "completed"


def _sink_runs(svc, request_id):
    """step_metrics records in the shared sink for one request id
    (empty before the daemon's first flush creates the file)."""
    try:
        text = pathlib.Path(svc.sink_path).read_text()
    except OSError:
        return []
    records = [json.loads(line) for line in text.splitlines()]
    return [r for r in records if r.get("kind") == "step_metrics"
            and (r.get("serving") or {}).get("request_id") == request_id]


# ----------------------------------------------------------- unit: faults

def test_circuit_breaker_state_machine():
    br = faults.CircuitBreaker(failures=2, cooloff_sec=0.2,
                               max_cooloff_sec=1.0)
    key = "spec-a"
    assert br.admit(key) == (True, 0.0, "closed")
    br.record_failure(key)
    assert br.admit(key)[0]                       # one failure: still closed
    br.record_failure(key)                        # second: opens
    allowed, retry_after, state = br.admit(key)
    assert (allowed, state) == (False, "open") and retry_after > 0
    assert br.fastfails == 1 and br.opens == 1
    time.sleep(0.25)                              # cool-off elapses
    allowed, _, state = br.admit(key)
    assert (allowed, state) == (True, "probe")    # half-open probe
    assert br.admit(key)[0] is False              # only ONE probe at a time
    br.record_failure(key)                        # probe fails: re-open,
    entry = br._keys[key]                         # cool-off doubled
    assert entry["state"] == "open" and entry["cooloff"] == 0.4
    time.sleep(0.45)
    allowed, _, state = br.admit(key)
    assert (allowed, state) == (True, "probe")
    br.record_success(key)                        # probe succeeds: closed
    assert br.state(key) == "closed" and br.closes == 1
    assert br.admit(key) == (True, 0.0, "closed")
    # abandoned probe frees the slot without a verdict
    br2 = faults.CircuitBreaker(failures=1, cooloff_sec=0.05)
    br2.record_failure(key)
    time.sleep(0.1)
    assert br2.admit(key)[2] == "probe"
    br2.abandon_probe(key)
    assert br2.admit(key)[2] == "probe"           # next request probes again
    # the key table is LRU-bounded against unique-spec storms
    br3 = faults.CircuitBreaker(failures=1, max_keys=4)
    for i in range(10):
        br3.record_failure(f"k{i}")
    assert len(br3._keys) == 4


def test_result_cache_lru_and_replay_count():
    cache = faults.ResultCache(size=2)
    cache.put("a", {"r": 1}, {"kind": "result"}, b"pa")
    cache.put("b", None, {"kind": "result"}, b"pb")
    assert cache.get("a")[2] == b"pa"            # touch: a is now MRU
    cache.put("c", None, {"kind": "result"}, b"pc")
    assert cache.get("b") is None                # LRU evicted
    assert cache.get("a") is not None and cache.get("c") is not None
    assert cache.replays == 3
    # fingerprint mismatch is a MISS: an id reused with different
    # spec/params must never serve another request's result
    cache.put("f", None, {"kind": "result"}, b"pf", fingerprint="abc")
    assert cache.get("f", fingerprint="abc") is not None
    assert cache.get("f", fingerprint="xyz") is None
    # byte budget: large payloads evict LRU entries past max_bytes, and
    # one oversized payload is refused rather than flushing the cache
    small = faults.ResultCache(size=16, max_bytes=100)
    small.put("x", None, {}, b"a" * 60)
    small.put("y", None, {}, b"b" * 60)          # 120 > 100: x evicted
    assert small.get("x") is None and small.get("y") is not None
    assert small.payload_bytes == 60
    small.put("huge", None, {}, b"c" * 200)      # oversized: refused
    assert small.get("huge") is None and small.get("y") is not None
    off = faults.ResultCache(size=0)
    off.put("a", None, {}, b"")
    assert off.get("a") is None                  # disabled


def test_retry_policy_jitter_bounds():
    pol = res_mod.RetryPolicy(base_delay=1.0, max_delay=10.0, jitter=0.25)
    for attempt in (1, 2, 3):
        base = min(1.0 * 2 ** (attempt - 1), 10.0)
        for _ in range(20):
            d = pol.delay(attempt)
            assert 0.75 * base <= d <= 1.25 * base
    deterministic = res_mod.RetryPolicy(base_delay=1.0)
    assert deterministic.delay(2) == 2.0         # jitter=0: exact


# --------------------------------------------------- admission / shedding

def test_overload_storm_sheds_with_retry_hint(daemon):
    """Over-capacity storm against queue_depth=1: excess requests get
    structured `overloaded` refusals carrying retry_after_sec, at least
    one request is served, and the daemon survives."""
    shed_before = daemon.shed
    header = {"kind": "run", "spec": DIFF, "dt": DT,
              "stop_iteration": 2500}
    payload = protocol.encode_fields(_ics())
    results = chaos_mod.queue_storm(daemon.port, header, payload=payload,
                                    n=5)
    assert all(r is not None for r in results)
    served = [r for r in results if r["ok"]]
    shed = [r for r in results if r["code"] == "overloaded"]
    assert served, "storm starved every request"
    assert shed, "5 concurrent requests against queue_depth=1 " \
                 "produced no overload shed"
    assert all(r["retry_after_sec"] is not None
               and r["retry_after_sec"] > 0 for r in shed)
    # shed replies are FAST (load shedding, not queueing)
    assert all(r["wall_sec"] < 5.0 for r in shed)
    assert daemon.shed - shed_before == len(shed)
    assert_healthy(daemon, "overload storm")


def test_mem_watermark_evicts_pool():
    """A 1 MiB RSS watermark (always exceeded) trims the warm pool to
    one entry before each build instead of letting entries accumulate
    toward an OOM — and requests still succeed."""
    with local_service(mem_watermark_mb=1, pool_size=4) as svc:
        client = ServiceClient(port=svc.port, timeout=120)
        for size in (SIZE, 16):
            spec = {"problem": "diffusion", "params": {"size": size}}
            x = np.linspace(0, 2 * np.pi, size, endpoint=False)
            result = client.run(spec, ics={"u": ("g", np.sin(x))}, dt=DT,
                                stop_iteration=3)
            assert result.result["stopped_by"] == "completed"
        # the third distinct request finds len(pool)==2 over the
        # watermark and must trim to one before building
        assert_healthy(svc, "memory watermark")
        assert len(svc.pool) <= 2
        assert svc.stats()["faults"]["mem_evictions"] >= 1


# --------------------------------------------------------------- deadlines

def test_deadline_expired_in_queue_fails_structurally():
    """A run whose deadline elapsed while it sat in the queue is refused
    at pop with `deadline-exceeded`, before any solver work."""
    svc = SolverService(port=0)
    a, b = socket.socketpair()
    with a:
        item = {"conn": b, "wfile": b.makefile("wb"),
                "header": {"kind": "run", "spec": DIFF, "dt": DT,
                           "stop_iteration": 5, "deadline_sec": 0.01},
                "payload": None, "t_accept": time.perf_counter() - 1.0,
                "deadline_mono": time.monotonic() - 0.5, "probe": False}
        svc._handle_run(item)
        header, _ = protocol.recv_frame(a.makefile("rb"))
    assert header["kind"] == "error"
    assert header["code"] == "deadline-exceeded"
    assert svc.deadline_exceeded == 1
    assert svc.pool.misses == 0                  # no build was attempted


def test_deadline_mid_run_stops_gracefully_with_checkpoint(daemon,
                                                           tmp_path):
    """A mid-run deadline stops the solve at a step boundary through the
    resilient loop: the client still gets telemetry + a result frame
    (`stopped_by: "deadline-exceeded"`), the final durable checkpoint is
    written and restores to the stop iteration, and the daemon serves
    the next request bit-identically."""
    ckpt = tmp_path / "ckpt"
    before = daemon.deadline_exceeded
    client = ServiceClient(port=daemon.port, timeout=120)
    result = client.run(DIFF, ics=_ics(), dt=1e-4, stop_iteration=10**6,
                        deadline_sec=0.6, checkpoint=str(ckpt))
    assert result.result["stopped_by"] == "deadline-exceeded"
    stopped_at = result.result["iteration"]
    assert 0 < stopped_at < 10**6
    assert result.serving["deadline_sec"] == 0.6
    assert result.record is not None             # telemetry still flushed
    assert daemon.deadline_exceeded - before == 1
    # the deadline-stop checkpoint restores the run exactly
    sets = sorted(ckpt.glob("*.h5"))
    assert sets, "no durable checkpoint written at the deadline stop"
    n_valid, reason = res_mod.validate_checkpoint(sets[-1])
    assert n_valid >= 1, reason
    solver = protocol.resolve_builder(DIFF)()
    event = res_mod.resume_latest(solver, ckpt)
    assert event is not None and solver.iteration == stopped_at
    assert_healthy(daemon, "deadline")


# ---------------------------------------------------------------- watchdog

def test_watchdog_fails_hung_step_and_replaces_executor(tmp_path):
    """A chaos-hung step (no step progress past watchdog_sec) fails the
    request with `watchdog-timeout`, emits a watchdog_postmortem record
    (thread stacks) to the sink, replaces the wedged executor thread,
    and the replacement serves the next request bit-identically."""
    sink = tmp_path / "served.jsonl"
    # the fire must land inside the chaos hang, not on a slow-but-
    # legitimate first step: a request's first steps load the programs of
    # the serving loop from the XLA cache (1.3 s on a host shared by six
    # test workers), which WATCHDOG_SEC is documented not to cover. So
    # one plain request runs them with nobody watching, and the watchdog
    # starts before the one that hangs: from there a legitimate step is
    # a millisecond, and where the fire found the run is counted below
    with local_service(prewarm=True, hold_watchdog=True, watchdog_sec=1.2,
                       chaos_enabled=True, sink=str(sink)) as svc:
        gen_before = svc._worker_gen
        client = ServiceClient(port=svc.port, timeout=120)
        client.run(DIFF, ics=_ics(), dt=DT, stop_iteration=STEPS)
        svc.start_watchdog()
        with pytest.raises(ServiceError) as excinfo:
            client.run(DIFF, ics=_ics(), dt=DT, stop_iteration=10**6,
                       chaos={"hang_iteration": 5, "hang_sec": 3.0})
        assert excinfo.value.code == "watchdog-timeout"
        stats = svc.stats()["faults"]
        assert stats["watchdog_fires"] == 1
        assert svc._worker_gen == gen_before + 1   # executor replaced
        # the suspect pool entry is quarantined: the wedged (stale)
        # executor may still hold its solver, so the replacement must
        # build fresh rather than share it
        assert len(svc.pool) == 0
        # postmortem record: request context + thread stacks
        records = [json.loads(line)
                   for line in sink.read_text().splitlines()]
        post = [r for r in records
                if r.get("kind") == "watchdog_postmortem"]
        assert len(post) == 1
        assert post[0]["stuck_sec"] >= 1.2
        assert post[0]["iteration"] == 5    # the hang's own iteration
        assert any("sleep" in s or "after_step" in s
                   for s in post[0]["stacks"]), \
            "postmortem stacks do not show the hung thread"
        # the replacement executor answers (and the stale one, once its
        # hang ends, unwinds via AbandonedRun without touching the queue).
        # Its entry was quarantined, so this is a cold start, which a
        # WATCHDOG_SEC of 1.2 is not sized for: the drill is over
        svc._watchdog.stop()
        assert_healthy(svc, "watchdog")
        # chaos injection is refused on a daemon without --chaos
        svc.chaos_enabled = False
        with pytest.raises(ServiceError) as refused:
            client.run(DIFF, ics=_ics(), dt=DT, stop_iteration=5,
                       chaos={"hang_iteration": 1, "hang_sec": 1.0})
        assert refused.value.code == "bad-spec"


# --------------------------------------------------------- circuit breaker

def test_circuit_breaker_isolates_poisoned_spec(daemon):
    """A spec whose build fails repeatedly trips its circuit: requests
    fast-fail with `circuit-open` (builder NOT invoked) during the
    cool-off, the half-open probe closes the circuit on success, and
    healthy specs are unaffected throughout. (The shared daemon runs
    breaker_failures=2, breaker_cooloff=0.5.)"""
    calls = {"n": 0}

    def flaky_builder(size=24):
        calls["n"] += 1
        if calls["n"] <= 2:
            raise RuntimeError("chaos: poisoned build")
        return protocol.PROBLEMS["diffusion"](size=size)

    protocol.register_problem("flaky_diffusion", flaky_builder)
    flaky = {"problem": "flaky_diffusion", "params": {"size": 24}}
    opens_before = daemon.breaker.opens
    try:
        client = ServiceClient(port=daemon.port, timeout=120)
        x24 = np.linspace(0, 2 * np.pi, 24, endpoint=False)
        ics24 = {"u": ("g", np.sin(x24))}
        for _ in range(2):                        # two consecutive failures
            with pytest.raises(ServiceError) as excinfo:
                client.run(flaky, ics=ics24, dt=DT, stop_iteration=5)
            assert excinfo.value.code == "build-failed"
        assert calls["n"] == 2
        with pytest.raises(ServiceError) as excinfo:  # circuit OPEN
            client.run(flaky, ics=ics24, dt=DT, stop_iteration=5)
        assert excinfo.value.code == "circuit-open"
        assert excinfo.value.retry_after_sec > 0
        assert calls["n"] == 2, "fast-fail still invoked the builder"
        # a healthy spec is served while the poisoned one cools off
        assert_healthy(daemon, "circuit-open")
        time.sleep(0.6)                           # cool-off elapses
        result = client.run(flaky, ics=ics24, dt=DT,
                            stop_iteration=5)     # half-open probe: builds
        assert calls["n"] == 3
        assert result.result["stopped_by"] == "completed"
        breaker = daemon.stats()["faults"]["breaker"]
        assert breaker["opens"] - opens_before == 1
        assert breaker["fastfails"] >= 1
        assert breaker["closes"] >= 1 and breaker["open"] == []
        result = client.run(flaky, ics=ics24, dt=DT,
                            stop_iteration=5)     # closed again: pool hit
        assert result.ack["pool_verdict"] == "hit"
    finally:
        protocol.PROBLEMS.pop("flaky_diffusion", None)


# -------------------------------------------------------- idempotent retry

def test_idempotent_retry_replays_after_dropped_result(daemon):
    """A client that vanishes before reading its result frame retries
    with the same request id and gets the COMPLETED outcome replayed
    from the result cache — bit-identical to the direct solve — instead
    of a re-run."""
    replays_before = daemon.results.replays
    payload = protocol.encode_fields(_ics())
    header = {"kind": "run", "spec": DIFF, "dt": DT,
              "stop_iteration": STEPS, "id": "retry-me-1"}
    # the client vanishes right after the ack — the daemon completes
    # the run (ON_CLIENT_DROP=complete) and caches the result
    frames = chaos_mod.vanish_client(daemon.port, header, payload=payload,
                                     read_frames=1)
    assert frames and frames[0]["kind"] == "ack"
    # the idempotent retry: same id, fresh connection
    client = ServiceClient(port=daemon.port, timeout=120)
    result = client.run(DIFF, ics=_ics(), dt=DT, stop_iteration=STEPS,
                        request_id="retry-me-1")
    assert result.replayed
    assert result.ack["pool_verdict"] == "replayed"
    layout, u = result.fields["u"]
    assert layout == "c"
    assert np.array_equal(u, direct_reference()), \
        "replayed result differs from the direct solve"
    assert daemon.results.replays > replays_before
    # exactly ONE solve ran for the id: one step_metrics record
    assert len(_sink_runs(daemon, "retry-me-1")) == 1, \
        "the retry re-ran the solve"
    # replaying again is also served from cache
    again = client.run(DIFF, dt=DT, stop_iteration=STEPS,
                       request_id="retry-me-1")
    assert again.replayed
    assert np.array_equal(again.fields["u"][1], u)
    # the SAME id with different run params must re-execute, not serve
    # the stale cached outcome
    mismatch = client.run(DIFF, ics=_ics(), dt=DT,
                          stop_iteration=STEPS + 2,
                          request_id="retry-me-1")
    assert not mismatch.replayed, \
        "an id reused with different params replayed a stale result"
    assert mismatch.result["iteration"] == STEPS + 2


# ------------------------------------------------------------- client drop

def test_client_disconnect_mid_stream_abort(tmp_path):
    """ON_CLIENT_DROP=abort: a dead client socket detected on a progress
    send stops the run at the next step boundary; telemetry for the run
    is flushed exactly once; the daemon stays healthy."""
    sink = tmp_path / "served.jsonl"
    with local_service(on_client_drop="abort", sink=str(sink),
                       prewarm=True) as svc:
        svc.sink_path = str(sink)
        payload = protocol.encode_fields(_ics())
        header = {"kind": "run", "spec": DIFF, "dt": 1e-4,
                  "stop_iteration": 10**6, "progress_every": 1,
                  "id": "dropper"}
        chaos_mod.vanish_client(svc.port, header, payload=payload,
                                read_frames=2)   # ack + one progress
        # poll for the SINK RECORD, not intermediate daemon state: the
        # active-run slot clears before the telemetry flush lands
        deadline = time.monotonic() + 60
        runs = []
        while time.monotonic() < deadline:
            runs = _sink_runs(svc, "dropper")
            if runs and svc.client_drops >= 1:
                break
            time.sleep(0.05)
        assert svc.stats()["faults"]["client_drops"] == 1
        assert len(runs) == 1, \
            f"telemetry flushed {len(runs)} times for the dropped run"
        # the abort stopped the run long before its 10^6 iterations
        assert runs[0]["iterations"] < 10**5
        # an ABORTED partial result must never be cached for replay: a
        # retry of the same id re-executes and completes
        client = ServiceClient(port=svc.port, timeout=120)
        retry = client.run(DIFF, ics=_ics(), dt=DT, stop_iteration=STEPS,
                           request_id="dropper")
        assert not retry.replayed, \
            "a client-drop-aborted partial result was replayed as done"
        assert retry.result["stopped_by"] == "completed"
        assert_healthy(svc, "client drop")


# -------------------------------------------------------- protocol abuse

def test_slow_loris_and_torn_frame_bounded_by_idle_timeout(daemon):
    """A slow-loris connection is expired by the ABSOLUTE request-read
    bound (IDLE_TIMEOUT_SEC — a byte-drip cannot reset it) with a
    structured error; a half-written frame (header promising a payload,
    then disconnect) is a structured truncation — and the daemon answers
    a healthy request bit-identically after both."""
    errors_before = daemon.errors
    t0 = time.monotonic()
    reply = chaos_mod.slow_loris(daemon.port, hold_sec=1.2)
    assert time.monotonic() - t0 < 30
    assert reply is None or reply.get("code") == "bad-frame"
    chaos_mod.half_frame(daemon.port, claim_bytes=4096)
    deadline = time.monotonic() + 10
    while daemon.errors < errors_before + 2 \
            and time.monotonic() < deadline:
        time.sleep(0.05)
    assert daemon.errors >= errors_before + 2
    client = ServiceClient(port=daemon.port, timeout=60)
    assert client.ping()["kind"] == "pong"
    assert_healthy(daemon, "slow-loris/torn-frame")


def test_sigkill_client_mid_run(daemon):
    """A real `submit` subprocess SIGKILLed mid-stream (no cooperative
    close): the daemon detects the dead peer on a later send, completes
    per ON_CLIENT_DROP=complete, and stays healthy."""
    served_before = daemon.requests_served
    proc = chaos_mod.sigkill_client(daemon.port, DIFF, dt=1e-4,
                                    stop_iteration=4000,
                                    after_progress_frames=1)
    assert proc.returncode == -signal.SIGKILL
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        if daemon._get_active_run() is None and daemon._queued_runs == 0 \
                and daemon.requests_served > served_before:
            break
        time.sleep(0.1)
    assert daemon.requests_served > served_before, \
        "daemon did not complete the orphaned run"
    assert_healthy(daemon, "SIGKILL'd client")


# -------------------------------------------------------- rolling restart

def _spawn_daemon(workdir, port):
    from conftest import register_daemon
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    stderr_path = os.path.join(workdir, f"daemon_{port}.err")
    stderr = open(stderr_path, "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "dedalus_tpu", "serve",
         "--port", str(port)],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=stderr,
        text=True)
    register_daemon(proc, stderr_path)
    return proc, stderr


def test_client_retry_survives_rolling_daemon_restart(tmp_path):
    """The satellite acceptance: kill the daemon and relaunch it on the
    same port mid-session; the client's jittered-backoff reconnect
    (`retries=` / `submit --retry`) makes the restart invisible — the
    second request succeeds against the relaunched daemon."""
    with socket.socket() as probe:              # reserve an ephemeral port
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
    workdir = str(tmp_path)
    proc1, stderr1 = _spawn_daemon(workdir, port)
    try:
        banner = json.loads(proc1.stdout.readline())
        assert banner["kind"] == "ready" and banner["port"] == port
        client = ServiceClient(port=port, timeout=120, retries=20,
                               retry_base_delay=0.5)
        r1 = client.run(DIFF, ics=_ics(), dt=DT, stop_iteration=STEPS)
        assert r1.result["stopped_by"] == "completed"
        # rolling restart: SIGKILL (no graceful drain) + relaunch
        proc1.kill()
        proc1.wait(timeout=30)
    finally:
        stderr1.close()
    proc2, stderr2 = _spawn_daemon(workdir, port)
    try:
        # no waiting for the ready banner: the CLIENT's reconnect loop
        # must ride out the boot window (connection refused -> retry)
        r2 = client.run(DIFF, ics=_ics(), dt=DT, stop_iteration=STEPS)
        assert r2.result["stopped_by"] == "completed"
        assert np.array_equal(r2.fields["u"][1], direct_reference()), \
            "post-restart served result differs from the direct solve"
        assert r2.attempts > 1, \
            "restart was supposedly invisible but no retry happened"
    finally:
        try:
            ServiceClient(port=port, timeout=30).shutdown()
            proc2.wait(timeout=60)
        except Exception:
            proc2.kill()
        stderr2.close()
