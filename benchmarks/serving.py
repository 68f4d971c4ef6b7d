"""
Serving benchmark: cold-miss vs warm-hit time-to-first-step, request
throughput, and overload behavior against a LIVE `python -m dedalus_tpu
serve` daemon subprocess — the served-latency numbers the warm pool
exists to buy, and the bounded-degradation numbers the admission
control exists to guarantee.

Three scenarios:

  rb256x64_serving      the 2-D Rayleigh-Benard flagship (compute-bound):
                        the acceptance bar — warm pool-hit
                        time-to-first-step >= 10x faster than a cold
                        fresh-process request — is checked here.
  diffusion64_serving   the 1-D forced heat equation (dispatch-bound):
                        ttfs plus a sequential request-throughput sweep.
  diffusion64_overload  a sustained closed-loop storm holding 2x the
                        daemon's in-system capacity outstanding against
                        a bounded queue: records the shed rate,
                        accepted-request p50/p95 latency (which must
                        stay under the (queue_depth+3) x single-request
                        bound — load shedding, not unbounded queueing),
                        and zero daemon restarts.
  diffusion64_batching  the continuous-batching multiplier: the same
                        closed-loop same-spec storm against the single-
                        executor baseline AND a `--batch` daemon whose
                        micro-batches coalesce it — requests/s, p50/p95
                        both modes, the speedup (>= 1.5x acceptance),
                        and the batch occupancy stats.
  router_scaling        the replica-fleet spec-locality multiplier: a
                        closed-loop MIXED-spec storm (6 distinct
                        problems, one pinned worker each) against
                        1/2/4-replica fleets behind the spec-hash
                        router (service/router.py), every replica
                        capped at --pool-size 3 so a lone replica
                        thrashes its warm pool on the mix while the
                        hash-partitioned fleet keeps every spec
                        resident — requests/s per fleet size, the 4v1
                        speedup (>= 2.5x acceptance), and the router's
                        forwarding overhead p50 (routed minus direct
                        warm request wall, 1-replica fleet).

Methodology: one fresh daemon per problem with an EMPTY private
assembly-cache directory, so the first request is a true cold
fresh-process request (host assembly + structure analysis + factor +
step compile all paid inside `time_to_first_step_sec`, which the server
measures dispatch -> first-step-complete). Subsequent identical requests
hit the warm pool; the warm ttfs is the median of WARM_RUNS requests.
All timings are the SERVER's served-latency fields (the client-observed
request wall rides along for context). Cold and warm runs use identical
initial conditions and the returned coefficient-layout fields are
compared bit-for-bit — the pool reset must reproduce the cold result
exactly or the speedup does not count.

Appends one row per problem to benchmarks/results.jsonl and exits
nonzero when the RB warm/cold ttfs ratio misses the 10x acceptance bar.

Run: python benchmarks/serving.py [--quick]
  --quick   diffusion only, fewer warm runs, no row appended (CI smoke).
"""

import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from dedalus_tpu.service.client import ServiceClient  # noqa: E402

T0 = time.time()
WARM_RUNS = 3
THROUGHPUT_REQUESTS = 10


def mark(msg):
    print(f"[serving {time.time() - T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def start_daemon(workdir, *extra):
    """Fresh daemon subprocess with an empty private assembly cache (a
    true cold start) and a JSONL sink inside `workdir`. Returns
    (proc, client, sink_path, stderr_file)."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["DEDALUS_TPU_ASSEMBLY_CACHE"] = os.path.join(workdir, "assembly")
    sink = os.path.join(workdir, "served.jsonl")
    stderr = open(os.path.join(workdir, "daemon.err"), "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "dedalus_tpu", "serve", "--sink", sink,
         *extra],
        env=env, stdout=subprocess.PIPE, stderr=stderr, text=True)
    line = proc.stdout.readline()
    try:
        banner = json.loads(line)
    except ValueError:
        proc.kill()
        raise RuntimeError(f"daemon failed to start: {line!r} (see "
                           f"{stderr.name})")
    mark(f"daemon ready on port {banner['port']} (pid {banner['pid']})")
    return proc, ServiceClient(port=banner["port"], timeout=1200), sink, \
        stderr


def stop_daemon(proc, client, stderr):
    try:
        client.shutdown()
        proc.wait(timeout=120)
    except Exception:
        proc.kill()
    finally:
        stderr.close()


def one_request(client, spec, ics, dt, steps, tag):
    t0 = time.perf_counter()
    result = client.run(spec, ics=ics, dt=dt, stop_iteration=steps)
    wall = time.perf_counter() - t0
    serving = result.serving
    mark(f"{tag}: pool={serving['pool_verdict']} "
         f"ttfs={serving['time_to_first_step_sec']}s "
         f"(request wall {wall:.2f}s)")
    return {
        "pool_verdict": serving["pool_verdict"],
        "ttfs_sec": serving["time_to_first_step_sec"],
        "queue_sec": serving["queue_sec"],
        "build_sec": serving.get("build_sec"),
        "request_wall_sec": round(wall, 4),
        "fields": result.fields,
        "steps_per_sec": (result.record or {}).get("steps_per_sec"),
        # the daemon-resolved plan rides back in the flushed step record
        "plan": (result.record or {}).get("plan"),
    }


def run_problem(config, spec, ics, dt, steps, warm_runs,
                throughput_requests=0):
    workdir = tempfile.mkdtemp(prefix="dedalus_serving_")
    proc, client, sink, stderr = start_daemon(workdir)
    try:
        cold = one_request(client, spec, ics, dt, steps, f"{config} cold")
        if cold["pool_verdict"] != "cold":
            # a shared ambient cache leaked in; the number would flatter
            # nothing (warm-cache is FASTER than cold) but the row must
            # say what it measured
            mark(f"WARNING: first request verdict is "
                 f"{cold['pool_verdict']}, not cold")
        warm = [one_request(client, spec, ics, dt, steps,
                            f"{config} warm-{i + 1}")
                for i in range(warm_runs)]
        assert all(w["pool_verdict"] == "hit" for w in warm), \
            "warm request missed the pool"
        # bit-identity: every warm result must equal the cold one
        names = sorted(cold["fields"])
        bit_identical = all(
            np.array_equal(w["fields"][name][1], cold["fields"][name][1])
            for w in warm for name in names)
        warm_ttfs = statistics.median(w["ttfs_sec"] for w in warm)
        row = {
            "config": config,
            "backend": os.environ.get("JAX_PLATFORMS", "cpu").split(",")[0],
            "dt": dt,
            "steps_per_request": steps,
            "cold_verdict": cold["pool_verdict"],
            "ttfs_cold_sec": round(cold["ttfs_sec"], 4),
            "ttfs_warm_sec": round(warm_ttfs, 4),
            "ttfs_warm_runs": [round(w["ttfs_sec"], 4) for w in warm],
            "ttfs_speedup": round(cold["ttfs_sec"] / warm_ttfs, 2)
            if warm_ttfs else None,
            "build_sec_cold": cold["build_sec"],
            "request_wall_cold_sec": cold["request_wall_sec"],
            "request_wall_warm_sec": round(statistics.median(
                w["request_wall_sec"] for w in warm), 4),
            "queue_sec_warm": round(statistics.median(
                w["queue_sec"] for w in warm), 6),
            "bit_identical_cold_warm": bool(bit_identical),
            "steps_per_sec_warm": warm[-1]["steps_per_sec"],
            "plan": warm[-1]["plan"] or cold["plan"],
        }
        if throughput_requests:
            mark(f"{config}: throughput sweep "
                 f"({throughput_requests} requests x {steps} steps)")
            t0 = time.perf_counter()
            for _ in range(throughput_requests):
                client.run(spec, ics=ics, dt=dt, stop_iteration=steps)
            wall = time.perf_counter() - t0
            row["throughput_requests"] = throughput_requests
            row["throughput_requests_per_sec"] = round(
                throughput_requests / wall, 2)
            row["throughput_member_steps_per_sec"] = round(
                throughput_requests * steps / wall, 1)
            mark(f"{config}: {row['throughput_requests_per_sec']} "
                 "requests/s")
        stats = client.stats()
        row["pool"] = {k: stats["pool"][k]
                       for k in ("hits", "misses", "evictions")}
        mark(f"{config}: ttfs cold {row['ttfs_cold_sec']}s -> warm "
             f"{row['ttfs_warm_sec']}s ({row['ttfs_speedup']}x), "
             f"bit-identical={row['bit_identical_cold_warm']}")
        return row
    finally:
        stop_daemon(proc, client, stderr)
        shutil.rmtree(workdir, ignore_errors=True)


def run_overload(config="diffusion64_overload", queue_depth=1,
                 storm_rate_x=2.0, rounds=8, steps=400):
    """Sustained over-capacity storm, CLOSED-LOOP: `storm_rate_x` times
    the daemon's in-system capacity (1 executing + queue_depth queued)
    in always-outstanding client workers, each re-submitting the moment
    its previous request resolves — so overload pressure is structural,
    not a product of timing calibration, and shedding MUST occur.
    Records the shed rate, accepted-request p50/p95 latency, the MAX
    live queue occupancy (a stats sampler polls the daemon's
    faults.queued throughout the storm — the direct no-unbounded-queue-
    growth observation), and that the daemon neither crashed nor
    restarted. Acceptance: max observed queue occupancy never exceeds
    queue_depth, shedding occurred, and accepted p95 stays under a
    1.5 x (queue_depth + 3) x single-request sanity bound (the
    admission bound caps the in-system population at queue_depth + 1
    service times; the headroom absorbs 2-core scheduling jitter
    between the daemon and the storm workers)."""
    import statistics as stats_mod
    import threading

    from dedalus_tpu.service.protocol import ServiceError

    spec = {"problem": "diffusion", "params": {"size": 64}}
    ics = diffusion_ics(64)
    capacity = queue_depth + 1
    workers = max(int(round(storm_rate_x * capacity)), capacity + 1)
    workdir = tempfile.mkdtemp(prefix="dedalus_overload_")
    proc, client, sink, stderr = start_daemon(
        workdir, "--queue-depth", str(queue_depth))
    try:
        # warm the pool (build + step compile + phase-sampler thunks),
        # then calibrate the single-request service time (median of 5)
        for _ in range(2):
            client.run(spec, ics=ics, dt=1e-3, stop_iteration=steps)
        samples = []
        for _ in range(5):
            t0 = time.perf_counter()
            client.run(spec, ics=ics, dt=1e-3, stop_iteration=steps)
            samples.append(time.perf_counter() - t0)
        single = stats_mod.median(samples)
        mark(f"{config}: single request {single:.3f}s; closed-loop storm "
             f"of {workers} workers x {rounds} rounds "
             f"({storm_rate_x}x the {capacity}-deep in-system capacity)")
        accepted, shed, other = [], [], []
        outcome_lock = threading.Lock()
        # live queue-occupancy sampler: control requests are answered on
        # reader threads even while the executor is saturated, so the
        # max observed faults.queued IS the no-unbounded-growth check
        max_queued = [0]
        storm_over = threading.Event()

        def sample_queue():
            sclient = ServiceClient(port=client.port, timeout=30)
            while not storm_over.wait(0.2):
                try:
                    queued = sclient.stats()["faults"]["queued"]
                    max_queued[0] = max(max_queued[0], queued)
                except Exception:
                    pass

        def one_worker(i):
            wclient = ServiceClient(port=client.port, timeout=1200)
            done = 0
            while done < rounds:
                t_req = time.perf_counter()
                try:
                    wclient.run(spec, ics=ics, dt=1e-3,
                                stop_iteration=steps)
                    with outcome_lock:
                        accepted.append(time.perf_counter() - t_req)
                    done += 1
                except ServiceError as exc:
                    if exc.code == "overloaded":
                        with outcome_lock:
                            shed.append(exc.retry_after_sec)
                        # honor (a fraction of) the shed hint, then
                        # re-offer the load — sustained over-capacity
                        time.sleep(min(exc.retry_after_sec or 0.5,
                                       2.0) * 0.3)
                    else:
                        with outcome_lock:
                            other.append(exc.code)
                        done += 1
                except OSError as exc:
                    with outcome_lock:
                        other.append(f"oserror:{exc.errno}")
                    done += 1

        threads = [threading.Thread(target=one_worker, args=(i,),
                                    daemon=True) for i in range(workers)]
        sampler = threading.Thread(target=sample_queue, daemon=True)
        sampler.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=1200)
        storm_over.set()
        sampler.join(timeout=60)
        assert not any(t.is_alive() for t in threads), "storm worker hung"
        restarts = 0 if proc.poll() is None else 1
        alive = False
        try:
            alive = client.ping().get("kind") == "pong"
        except Exception:
            pass
        lats = sorted(accepted)
        p50 = lats[len(lats) // 2] if lats else None
        p95 = lats[min(int(len(lats) * 0.95), len(lats) - 1)] \
            if lats else None
        bound = 1.5 * (queue_depth + 3) * single
        # every issued request counts, so the row's fields stay mutually
        # consistent even when some workers hit non-shed errors
        total = len(accepted) + len(shed) + len(other)
        row = {
            "config": config,
            "backend": os.environ.get("JAX_PLATFORMS", "cpu").split(",")[0],
            "queue_depth": queue_depth,
            "storm_rate_x": storm_rate_x,
            "storm_workers": workers,
            "steps_per_request": steps,
            "requests_sent": total,
            "accepted": len(accepted),
            "shed": len(shed),
            "other_errors": len(other),
            "shed_rate": round(len(shed) / total, 3) if total else None,
            "single_request_sec": round(single, 4),
            "accepted_p50_sec": round(p50, 4) if p50 else None,
            "accepted_p95_sec": round(p95, 4) if p95 else None,
            "latency_bound_sec": round(bound, 4),
            "latency_bounded": bool(lats) and p95 <= bound,
            "max_queued_observed": max_queued[0],
            "queue_bounded": max_queued[0] <= queue_depth,
            "shed_with_retry_hint": sum(1 for s in shed if s),
            "daemon_restarts": restarts,
            "daemon_alive_after": alive,
        }
        mark(f"{config}: {len(accepted)} accepted / {len(shed)} shed / "
             f"{len(other)} other, p50 {row['accepted_p50_sec']}s p95 "
             f"{row['accepted_p95_sec']}s (bound {row['latency_bound_sec']}"
             f"s), max queued {max_queued[0]}/{queue_depth}, "
             f"restarts={restarts}, alive={alive}")
        return row
    finally:
        stop_daemon(proc, client, stderr)
        shutil.rmtree(workdir, ignore_errors=True)


def run_batching(config="diffusion64_batching", clients=8, rounds=4,
                 steps=400):
    """Continuous-batching throughput: a CLOSED-LOOP storm of `clients`
    concurrent same-spec workers (each re-submitting the moment its
    previous request resolves, with per-worker ICs — the batched
    operands) against (a) the single-executor baseline daemon and (b) a
    `--batch` daemon whose micro-batches coalesce the storm. The queue
    is deep enough that nothing sheds — this measures throughput and
    accepted latency, not admission control (run_overload covers that).
    Records requests/s and p50/p95 for both modes plus the multiplier,
    and the batch daemon's occupancy stats (batches formed, late joins,
    peak seats). Exits nonzero when batching is not at least 1.5x the
    single-executor requests/s — the multiplier IS the feature."""
    import threading

    spec = {"problem": "diffusion", "params": {"size": 64}}
    x = np.linspace(0, 2 * np.pi, 64, endpoint=False)
    worker_ics = [{"u": ("g", np.sin((1 + i % 4) * x)),
                   "a": ("g", 0.05 * (1 + i) * np.cos(x))}
                  for i in range(clients)]

    def storm(port):
        lat, errors = [], []
        lock = threading.Lock()

        def one_worker(i):
            wclient = ServiceClient(port=port, timeout=1200)
            for _ in range(rounds):
                t_req = time.perf_counter()
                try:
                    wclient.run(spec, ics=worker_ics[i], dt=1e-3,
                                stop_iteration=steps)
                    with lock:
                        lat.append(time.perf_counter() - t_req)
                except Exception as exc:
                    with lock:
                        errors.append(str(exc))
        threads = [threading.Thread(target=one_worker, args=(i,),
                                    daemon=True) for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=1200)
        wall = time.perf_counter() - t0
        assert not any(t.is_alive() for t in threads), "storm worker hung"
        lats = sorted(lat)
        return {
            "requests": len(lat),
            "errors": len(errors),
            "wall_sec": round(wall, 3),
            "requests_per_sec": round(len(lat) / wall, 3) if wall else 0,
            "p50_sec": round(lats[len(lats) // 2], 4) if lats else None,
            "p95_sec": round(lats[min(int(len(lats) * 0.95),
                                      len(lats) - 1)], 4)
            if lats else None,
        }

    out = {}
    for mode, extra in (("baseline", ()),
                        ("batched", ("--batch",
                                     "--batch-max", str(clients),
                                     "--batch-window", "0.02"))):
        workdir = tempfile.mkdtemp(prefix=f"dedalus_batching_{mode}_")
        proc, client, sink, stderr = start_daemon(
            workdir, "--queue-depth", str(2 * clients), *extra)
        try:
            # warm the pool (and, batched, the fleet programs) before
            # the measured storm
            for _ in range(2):
                client.run(spec, ics=worker_ics[0], dt=1e-3,
                           stop_iteration=steps)
            # occupancy is recorded as a STORM-ONLY delta: the daemon's
            # counters are cumulative and the two warmup requests formed
            # their own one-member batches
            pre = (client.stats()["serving"]["batching"]
                   if mode == "batched" else {})
            mark(f"{config}: {mode} storm ({clients} workers x {rounds} "
                 f"rounds x {steps} steps)")
            out[mode] = storm(client.port)
            if mode == "batched":
                post = client.stats()["serving"]["batching"]
                out["batch_stats"] = {
                    "batches": post["batches"] - pre["batches"],
                    "members": post["members"] - pre["members"],
                    "late_joins": post["late_joins"] - pre["late_joins"],
                    "peak_members": post["peak_members"],
                }
            out[mode]["daemon_crashed"] = proc.poll() is not None
            mark(f"{config}: {mode} {out[mode]['requests_per_sec']} "
                 f"requests/s (p50 {out[mode]['p50_sec']}s, p95 "
                 f"{out[mode]['p95_sec']}s, {out[mode]['errors']} errors)")
        finally:
            stop_daemon(proc, client, stderr)
            shutil.rmtree(workdir, ignore_errors=True)
    base_rps = out["baseline"]["requests_per_sec"] or 1e-9
    speedup = round(out["batched"]["requests_per_sec"] / base_rps, 2)
    batch_stats = out.get("batch_stats") or {}
    row = {
        "config": config,
        "backend": os.environ.get("JAX_PLATFORMS", "cpu").split(",")[0],
        "clients": clients,
        "rounds": rounds,
        "steps_per_request": steps,
        "baseline_requests_per_sec": out["baseline"]["requests_per_sec"],
        "baseline_p50_sec": out["baseline"]["p50_sec"],
        "baseline_p95_sec": out["baseline"]["p95_sec"],
        "batched_requests_per_sec": out["batched"]["requests_per_sec"],
        "batched_p50_sec": out["batched"]["p50_sec"],
        "batched_p95_sec": out["batched"]["p95_sec"],
        "requests_speedup": speedup,
        "errors": out["baseline"]["errors"] + out["batched"]["errors"],
        "batches": batch_stats.get("batches"),
        "late_joins": batch_stats.get("late_joins"),
        "peak_batch_members": batch_stats.get("peak_members"),
        "meets_1p5x": speedup >= 1.5
        and not out["batched"]["daemon_crashed"],
    }
    mark(f"{config}: batching {row['batched_requests_per_sec']} vs "
         f"baseline {row['baseline_requests_per_sec']} requests/s = "
         f"{speedup}x ({row['batches']} batches, {row['late_joins']} "
         f"late joins, peak {row['peak_batch_members']} seats)")
    return row


def _balanced_specs(count=6, per_replica=2):
    """`count` distinct diffusion specs whose 4-replica ring assignment
    (deterministic: the ring depends only on names+vnodes) spreads at
    most `per_replica` specs per replica — so the row measures the
    LOCALITY multiplier, not one-off hash luck with an adversarial
    spec set that happens to pile onto a single member."""
    from dedalus_tpu.service.router import (ring_order, ring_points,
                                            route_digest)
    points = ring_points(["r0", "r1", "r2", "r3"], 64)
    chosen, load = [], {}
    for size in range(40, 400, 4):
        spec = {"problem": "diffusion", "params": {"size": size}}
        owner = ring_order(points, route_digest({"spec": spec}))[0]
        if load.get(owner, 0) >= per_replica:
            continue
        load[owner] = load.get(owner, 0) + 1
        chosen.append(spec)
        if len(chosen) == count:
            return chosen
    raise RuntimeError("could not assemble a balanced spec set")


def _start_router(n_replicas, workdir, pool_size, queue_depth):
    """An in-process RouterService fronting `n_replicas` spawned
    daemons. Returns (router, serve_thread)."""
    import io
    import threading

    from dedalus_tpu.service.router import RouterService

    router = RouterService(
        replicas=n_replicas, workdir=workdir,
        replica_args=["--pool-size", str(pool_size),
                      "--queue-depth", str(queue_depth)],
        probe_sec=0.5, probe_timeout=5.0, wedge_misses=8)
    thread = threading.Thread(
        target=router.serve_forever, kwargs={"ready_stream": io.StringIO()},
        daemon=True)
    thread.start()
    deadline = time.monotonic() + 600
    while router.port == 0 or router._listener is None \
            or len(router.fleet.routable()) < n_replicas:
        if not thread.is_alive() or time.monotonic() > deadline:
            raise RuntimeError(f"{n_replicas}-replica fleet failed to "
                               f"come up (see {workdir})")
        time.sleep(0.1)
    return router, thread


def _stop_router(router, thread):
    router.request_drain("benchmark done")
    thread.join(timeout=300)


def run_router_scaling(config="router_scaling", fleet_sizes=(1, 2, 4),
                       specs=6, rounds=3, steps=200, pool_size=3,
                       overhead_probes=10):
    """Spec-locality scaling behind the replica router: the same
    closed-loop mixed-spec storm (one pinned worker per spec, each
    re-submitting the moment its previous request resolves) against
    1/2/4-replica fleets. Every replica's warm pool holds `pool_size`
    solvers, fewer than the spec mix — a lone replica evicts and
    rebuilds on nearly every arrival, while the spec-hash ring gives
    each fleet member a subset that FITS, so the multiplier measures
    warm-pool residency bought by routing, not extra cores. Also
    records the router's forwarding overhead (routed minus direct warm
    request wall p50, measured on the 1-replica fleet where both paths
    hit the same warm pool). Acceptance: >= 2.5x requests/s at 4
    replicas vs 1."""
    import statistics as stats_mod
    import threading

    spec_list = _balanced_specs(count=specs, per_replica=pool_size - 1)
    ics_list = [diffusion_ics(s["params"]["size"]) for s in spec_list]
    workdir = tempfile.mkdtemp(prefix="dedalus_router_")
    # one private assembly cache shared by every topology: the storm
    # measures in-process warm-POOL residency, which the on-disk cache
    # cannot provide, and sharing keeps later topologies' warmup short
    saved_cache = os.environ.get("DEDALUS_TPU_ASSEMBLY_CACHE")
    os.environ["DEDALUS_TPU_ASSEMBLY_CACHE"] = os.path.join(
        workdir, "assembly")

    def storm(port):
        lat, errors = [], []
        lock = threading.Lock()

        def one_worker(i):
            wclient = ServiceClient(port=port, timeout=1200)
            for _ in range(rounds):
                t_req = time.perf_counter()
                try:
                    wclient.run(spec_list[i], ics=ics_list[i], dt=1e-3,
                                stop_iteration=steps)
                    with lock:
                        lat.append(time.perf_counter() - t_req)
                except Exception as exc:
                    with lock:
                        errors.append(str(exc))
        threads = [threading.Thread(target=one_worker, args=(i,),
                                    daemon=True)
                   for i in range(len(spec_list))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=1200)
        wall = time.perf_counter() - t0
        assert not any(t.is_alive() for t in threads), "storm worker hung"
        lats = sorted(lat)
        return {"requests": len(lat), "errors": errors,
                "wall_sec": round(wall, 3),
                "requests_per_sec": round(len(lat) / wall, 3)
                if wall else 0,
                "p50_sec": round(lats[len(lats) // 2], 4)
                if lats else None}

    per_fleet = {}
    overhead_ms = None
    try:
        for n in fleet_sizes:
            subdir = os.path.join(workdir, f"fleet{n}")
            os.makedirs(subdir, exist_ok=True)
            router, thread = _start_router(n, subdir, pool_size,
                                           queue_depth=2 * len(spec_list))
            try:
                mark(f"{config}: warming {len(spec_list)} specs on the "
                     f"{n}-replica fleet")
                for spec, ics in zip(spec_list, ics_list):
                    ServiceClient(port=router.port, timeout=1200).run(
                        spec, ics=ics, dt=1e-3, stop_iteration=steps)
                mark(f"{config}: {n}-replica storm ({len(spec_list)} "
                     f"pinned workers x {rounds} rounds x {steps} steps)")
                per_fleet[n] = storm(router.port)
                per_fleet[n]["forward_p50_ms"] = \
                    router.stats()["router"]["forward"]["p50_ms"]
                mark(f"{config}: {n} replica(s) -> "
                     f"{per_fleet[n]['requests_per_sec']} requests/s "
                     f"({len(per_fleet[n]['errors'])} errors)")
                if n == 1 and overhead_probes:
                    # routed vs direct warm request wall, same replica,
                    # same warm pool: the difference IS the router
                    host, port = router.fleet.endpoint(
                        router.fleet.routable()[0])
                    spec, ics = spec_list[0], ics_list[0]

                    def p50_wall(client):
                        samples = []
                        for _ in range(overhead_probes):
                            t0 = time.perf_counter()
                            client.run(spec, ics=ics, dt=1e-3,
                                       stop_iteration=steps)
                            samples.append(time.perf_counter() - t0)
                        return stats_mod.median(samples)

                    routed = p50_wall(ServiceClient(port=router.port,
                                                    timeout=1200))
                    direct = p50_wall(ServiceClient(host=host, port=port,
                                                    timeout=1200))
                    overhead_ms = round(max(routed - direct, 0.0) * 1e3,
                                        3)
                    mark(f"{config}: forward overhead p50 "
                         f"{overhead_ms} ms (routed {routed:.4f}s vs "
                         f"direct {direct:.4f}s)")
            finally:
                _stop_router(router, thread)
    finally:
        if saved_cache is None:
            os.environ.pop("DEDALUS_TPU_ASSEMBLY_CACHE", None)
        else:
            os.environ["DEDALUS_TPU_ASSEMBLY_CACHE"] = saved_cache
        shutil.rmtree(workdir, ignore_errors=True)

    biggest, smallest = max(per_fleet), min(per_fleet)
    base_rps = per_fleet[smallest]["requests_per_sec"] or 1e-9
    speedup = round(per_fleet[biggest]["requests_per_sec"] / base_rps, 2)
    row = {
        "config": config,
        "backend": os.environ.get("JAX_PLATFORMS", "cpu").split(",")[0],
        # perfwatch-tracked measurement triplet: the 4-replica storm rate
        "metric": f"router_requests_per_sec_{biggest}r",
        "value": per_fleet[biggest]["requests_per_sec"],
        "unit": "requests/sec",
        "specs": len(spec_list),
        "clients": len(spec_list),
        "rounds": rounds,
        "steps_per_request": steps,
        "pool_size": pool_size,
        "replica_requests_per_sec": {
            str(n): per_fleet[n]["requests_per_sec"] for n in per_fleet},
        "replica_p50_sec": {str(n): per_fleet[n]["p50_sec"]
                            for n in per_fleet},
        f"requests_speedup_{biggest}v{smallest}": speedup,
        "forward_overhead_p50_ms": overhead_ms,
        "errors": sum(len(per_fleet[n]["errors"]) for n in per_fleet),
        "meets_2p5x": speedup >= 2.5
        and not any(per_fleet[n]["errors"] for n in per_fleet),
    }
    mark(f"{config}: " + ", ".join(
        f"{n}r={per_fleet[n]['requests_per_sec']}"
        for n in sorted(per_fleet)) +
        f" requests/s -> {speedup}x at {biggest} replicas "
        f"(forward overhead p50 {overhead_ms} ms)")
    return row


def diffusion_ics(size=64):
    x = np.linspace(0, 2 * np.pi, size, endpoint=False)
    return {"u": ("g", np.sin(3 * x)), "a": ("g", 0.1 * np.cos(x))}


def rb_ics(Nx=256, Nz=64):
    rng = np.random.default_rng(42)
    return {"b": ("g", 1e-3 * rng.standard_normal((Nx, Nz)))}


def main():
    quick = "--quick" in sys.argv
    from __graft_entry__ import _append_result
    if quick:
        # smoke mode appends nothing: a short-window quick row would
        # shadow the full measurement in bench.py's _attach_serving
        _append_result = lambda record: None  # noqa: E731

    rows = [run_problem(
        "diffusion64_serving",
        {"problem": "diffusion", "params": {"size": 64}},
        diffusion_ics(64), dt=1e-3, steps=25,
        warm_runs=2 if quick else WARM_RUNS,
        throughput_requests=4 if quick else THROUGHPUT_REQUESTS)]
    if not quick:
        rows.append(run_problem(
            "rb256x64_serving",
            # "banded" is forced here, as in coldstart.py (bench.py passes
            # nothing: `auto` is dense under 1 GiB of pencil matrices, so
            # RB 256x64 runs DenseOps there, and banded above); on a CPU
            # the dense path would make the first step itself seconds of
            # wall time and measure the matsolver, not the pool
            {"problem": "rayleigh_benard",
             "params": {"Nx": 256, "Nz": 64, "matsolver": "banded"}},
            rb_ics(), dt=0.01, steps=3, warm_runs=WARM_RUNS))
    ok = True
    for row in rows:
        row["meets_10x"] = (row.get("ttfs_speedup") or 0) >= 10.0 \
            and row["bit_identical_cold_warm"]
        if row["config"].startswith("rb"):
            ok = row["meets_10x"]
        _append_result(row)
        print(json.dumps(row), flush=True)
    # the closed-loop storm holds 2x the in-system capacity outstanding,
    # so shedding is structural; quick mode just shrinks the rounds.
    # queue_depth=1 keeps the client-side thread count (2x capacity = 4
    # workers) small enough that benchmark-process contention does not
    # pollute the accepted-latency measurement on a 2-core box.
    overload = run_overload(rounds=3 if quick else 8,
                            steps=200 if quick else 400)
    overload["bounded_under_overload"] = (
        overload["latency_bounded"] and overload["queue_bounded"]
        and overload["daemon_restarts"] == 0
        and overload["shed"] > 0 and overload["daemon_alive_after"])
    _append_result(overload)
    print(json.dumps(overload), flush=True)
    # the continuous-batching multiplier: same-spec closed-loop storm,
    # single-executor baseline vs `--batch` micro-batching
    batching_row = run_batching(clients=4 if quick else 8,
                                rounds=2 if quick else 4,
                                steps=200 if quick else 400)
    _append_result(batching_row)
    print(json.dumps(batching_row), flush=True)
    # the replica-fleet spec-locality multiplier: mixed-spec closed-loop
    # storm against 1/2/4-replica fleets behind the spec-hash router
    scaling_row = run_router_scaling(
        fleet_sizes=(1, 4) if quick else (1, 2, 4),
        rounds=2 if quick else 3,
        steps=100 if quick else 200)
    _append_result(scaling_row)
    print(json.dumps(scaling_row), flush=True)
    if not quick and not scaling_row["meets_2p5x"]:
        mark("FAIL: 4-replica fleet is not >= 2.5x single-replica "
             "requests/s under the mixed-spec storm")
        sys.exit(1)
    if not quick and not batching_row["meets_1p5x"]:
        mark("FAIL: batched serving is not >= 1.5x single-executor "
             "requests/s under the same-spec storm")
        sys.exit(1)
    if not quick and not ok:
        mark("FAIL: RB warm pool-hit ttfs is not >= 10x faster than the "
             "cold fresh-process request (or results drifted)")
        sys.exit(1)
    if not quick and not overload["bounded_under_overload"]:
        mark("FAIL: overload storm was not bounded (accepted p95 over the "
             "bound, no shedding, or the daemon crashed)")
        sys.exit(1)


if __name__ == "__main__":
    main()
