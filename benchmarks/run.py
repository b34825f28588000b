"""Benchmark harness entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Consumes the profiled
sample cache (generated on first run; a cached run takes ~2-4 min, a cold
run also profiles the 39-program suite).

    PYTHONPATH=src python -m benchmarks.run [--programs a,b] [--datasets N]
    PYTHONPATH=src python -m benchmarks.run --quick    # tiny subset
    PYTHONPATH=src python -m benchmarks.run --compare-backends  # executor A/B
    PYTHONPATH=src python -m benchmarks.run --serve-concurrent  # engine A/B
    PYTHONPATH=src python -m benchmarks.run --serve-oracle --tenants 3
                                # steady-state regret vs the per-workload
                                # oracle -> BENCH_oracle.json
    PYTHONPATH=src python -m benchmarks.run --serve-trace
                                # virtual-time tail-latency trace replay
                                # (10^5 requests) -> BENCH_latency.json

A dry-run roofline summary (from benchmarks/data/dryrun/*.json, produced
by benchmarks/dryrun_sweep.py) is appended when available.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import glob
import json
import multiprocessing
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

# CPU-serving thread discipline for the engine A/B: one intra-op thread
# per request, scale across concurrent requests (the standard production
# CPU-inference configuration).  Must be set before jaxlib creates its
# client, hence before the imports below; applies to BOTH engines, so it
# is a deployment mode, not a thumb on the scale.
if ("--serve-concurrent" in sys.argv or "--serve-oracle" in sys.argv
        or "--serve-real-trace" in sys.argv or "--serve-chaos" in sys.argv
        or "--serve-fleet" in sys.argv):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_cpu_multi_thread_eigen=false"
                                 " intra_op_parallelism_threads=1")

import numpy as np  # noqa: E402

from repro.core import dataset as ds  # noqa: E402
from repro.core.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.backends import list_backends  # noqa: E402
from repro.core.stream_config import StreamConfig  # noqa: E402
from repro.core.streams import (StreamedRunner,  # noqa: E402
                                profile_grid_interleaved)
from repro.core.workloads import get_workload  # noqa: E402

from benchmarks import paper_figures as pf  # noqa: E402

QUICK_PROGRAMS = ["vecadd", "binomial", "sgemm", "jacobi-1d", "mri-q",
                  "blackscholes", "dotprod", "fwt"]

COMPARE_PROGRAMS = ["vecadd", "sgemm", "blackscholes"]
COMPARE_CONFIGS = [StreamConfig(1, 8), StreamConfig(4, 8),
                   StreamConfig(8, 16)]

SERVE_PROGRAMS = ["vecadd", "dotprod", "mvmult"]
STATIC_GRID = [StreamConfig(1, 1), StreamConfig(1, 4), StreamConfig(2, 4),
               StreamConfig(4, 8)]


def compare_backends(programs=None, *, reps: int = 3) -> list[str]:
    """Executor-backend A/B: every runner backend on the same
    (workload, config) cells, vs the host-sync reference."""
    rows = []
    for prog in programs or COMPARE_PROGRAMS:
        wl = get_workload(prog)
        scale = wl.datasets[-1]
        chunked, shared = wl.make_data(scale, np.random.default_rng(0))
        runners = {name: StreamedRunner(wl, chunked, shared, backend=name)
                   for name in list_backends(kind="runner")}
        for cfg in COMPARE_CONFIGS:
            base = runners["host-sync"].run(cfg, reps=reps)
            for name, runner in runners.items():
                t = base if name == "host-sync" else runner.run(cfg,
                                                                reps=reps)
                rows.append(
                    f"backends.{prog}@{scale}.{cfg.partitions}x{cfg.tasks}"
                    f".{name},{t*1e6:.0f},vs_sync={base/t:.3f}x")
    return rows


def serve_trace(programs=None, *, n_requests: int = 12,
                backend: str = "host-sync",
                json_path: str | None = None) -> list[str]:
    """Static-best-config vs adaptive scheduling under the same mixed
    multi-tenant trace.

    The static deployment picks ONE config for the whole fleet — the
    grid point with the best summed runtime over each workload's first
    occurrence (the realistic offline choice) — and serves every request
    with it.  The adaptive scheduler makes a per-request decision
    (model search on cold miss, cache hit after) and self-corrects via
    telemetry-driven refinement.
    """
    from repro.serving import (AdaptiveScheduler, DriftDetector,
                               OverlapHeuristicModel, TelemetryLog,
                               make_trace)

    programs = programs or SERVE_PROGRAMS
    occurrences = -(-n_requests // len(programs))

    rows = []

    # --- static: one fixed config chosen offline, applied to all ---------
    trace = make_trace(programs, occurrences=occurrences)[:n_requests]
    first = {}
    for req in trace:
        first.setdefault(req.workload, req)
    runners = {name: StreamedRunner(get_workload(name), req.chunked,
                                    req.shared, backend=backend)
               for name, req in first.items()}
    min_rows = min(next(iter(r.chunked.values())).shape[0]
                   for r in runners.values())
    grid_cost = {}
    for cfg in STATIC_GRID:
        if cfg.partitions * cfg.tasks > min_rows:
            continue
        grid_cost[cfg] = sum(r.run(cfg, reps=2) for r in runners.values())
    static_cfg = min(grid_cost, key=grid_cost.get)

    t0 = time.perf_counter()
    static_total = 0.0
    for req in trace:
        runner = StreamedRunner(get_workload(req.workload), req.chunked,
                                req.shared, backend=backend)
        static_total += runner.run(static_cfg, reps=1, warmed=True)
    static_wall = time.perf_counter() - t0
    rows.append(f"serve.static.{static_cfg.partitions}x{static_cfg.tasks}"
                f".{backend},{static_total/len(trace)*1e6:.0f},"
                f"total_ms={static_total*1e3:.1f}")

    # --- adaptive: per-request decision + telemetry + refinement ---------
    trace = make_trace(programs, occurrences=occurrences)[:n_requests]
    # a tight drift threshold: the zero-training heuristic model WILL
    # mispredict some buckets, and the point of the comparison is that
    # telemetry-driven refinement re-profiles and corrects them online
    sched = AdaptiveScheduler(OverlapHeuristicModel(), backend=backend,
                              drift=DriftDetector(threshold=0.75,
                                                  min_samples=2),
                              telemetry=TelemetryLog(), keep_outputs=False)
    sched.submit_all(trace)
    t0 = time.perf_counter()
    results = sched.run()
    adaptive_wall = time.perf_counter() - t0
    adaptive_total = sum(r.measured_s for r in results)
    # steady state: the last round, after caches are warm and drift
    # refinements have corrected any mispredicted bucket
    tail = results[-len(programs):]
    steady_us = sum(r.measured_s for r in tail) / len(tail) * 1e6
    summary = sched.telemetry.summary()
    rows.append(f"serve.adaptive.{backend},"
                f"{adaptive_total/len(results)*1e6:.0f},"
                f"total_ms={adaptive_total*1e3:.1f},"
                f"steady_us={steady_us:.0f},"
                f"hit_rate={summary['hit_rate']:.2f},"
                f"refinements={summary['refinements']},"
                f"vs_static={static_total/max(adaptive_total, 1e-12):.3f}x")

    if json_path:
        payload = {
            "programs": programs,
            "n_requests": n_requests,
            "backend": backend,
            "static": {"config": static_cfg.as_tuple(),
                       "total_s": static_total, "wall_s": static_wall},
            "adaptive": {"total_s": adaptive_total,
                         "wall_s": adaptive_wall, **summary},
            "telemetry": [s.to_json() for s in sched.telemetry],
        }
        os.makedirs(os.path.dirname(os.path.abspath(json_path)),
                    exist_ok=True)
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1)
        rows.append(f"# serve JSON written to {json_path}")
    return rows


SERVE_CONCURRENT_PROGRAMS = ["binomial", "deriche", "mri-q"]


def _parallel_capacity(programs, scale_index, workers, *,
                       reps: int = 8) -> float:
    """Calibrate the box: how much does raw kernel execution speed up
    when issued from ``workers`` threads instead of one?  Uses the
    trace's own kernels (compiled + device-resident, max-of-2 trials;
    the timing core is :func:`repro.core.streams.parallel_capacity`,
    shared with the engine's load-aware drift calibration), so the
    number is the hardware ceiling the engine is chasing — on a
    steal-heavy 2-vCPU container this can be well under the thread
    count, and the engine can't beat physics."""
    import jax

    from repro.core.streams import parallel_capacity
    from repro.core.workloads import get_workload

    calls = []
    for name in programs:
        wl = get_workload(name)
        scale = wl.datasets[min(scale_index, len(wl.datasets) - 1)]
        chunked, shared = wl.make_data(scale, np.random.default_rng(0))
        jitk = jax.jit(wl.kernel)
        dev = jax.device_put(chunked)
        sh = jax.device_put(shared)
        jax.block_until_ready(jitk(dev, sh))        # compile, untimed

        def call(jitk=jitk, dev=dev, sh=sh):
            jax.block_until_ready(jitk(dev, sh))
        calls.append(call)

    return parallel_capacity(calls, workers, reps=reps)


def serve_concurrent_trace(programs=None, *, n_requests: int = 18,
                           backend: str = "host-sync", window: int = 8,
                           workers: int | None = None, scale_index: int = 8,
                           reps: int = 3,
                           json_path: str = "BENCH_serving.json") -> list[str]:
    """Long-trace steady-state throughput: the serial AdaptiveScheduler
    vs the concurrent engine on the SAME mixed multi-tenant trace.

    Fairness protocol:
      * one intra-op XLA thread (env set at module import) — both
        engines run the standard CPU-serving thread discipline, so
        request-level overlap is the only concurrency axis;
      * a shared decision pass first populates ONE TuningCache and the
        process-global compile caches — both timed engines then serve
        all-warm-hit traces with IDENTICAL per-request configs, so the
        A/B measures the engines, not model noise or compile warmth;
      * min wall over ``reps`` timed runs per engine (steal-time spikes
        on shared boxes otherwise decide the result);
      * a calibration probe reports the box's raw ``workers``-thread
        kernel-scaling ceiling next to the speedup —
        ``capacity_fraction`` says how much of the achievable overlap
        the engine delivers.

    Results land in ``BENCH_serving.json`` — the serving perf
    trajectory's first point.
    """
    from repro.core.autotuner import TuningCache
    from repro.serving import (AdaptiveScheduler, ConcurrentScheduler,
                               DriftDetector, OverlapHeuristicModel,
                               TelemetryLog, make_trace)

    programs = programs or SERVE_CONCURRENT_PROGRAMS
    workers = workers or max(2, min(window, os.cpu_count() or 2))
    occurrences = -(-n_requests // len(programs))
    # a lenient drift threshold on BOTH sides: concurrent measured_s is
    # wall time under contention, and a refinement storm mid-trace would
    # benchmark the refiner, not the engines
    cache = TuningCache()

    def sched_kwargs():
        return dict(backend=backend, cache=cache,
                    drift=DriftDetector(threshold=1e9),
                    telemetry=TelemetryLog(), keep_outputs=False)

    def trace():
        return make_trace(programs, occurrences=occurrences,
                          scale_index=scale_index)[:n_requests]

    rows = []
    # shared decision pass: cold-tunes every bucket into the shared
    # cache and warms the process-global compile caches, untimed
    decide = AdaptiveScheduler(OverlapHeuristicModel(), **sched_kwargs())
    decide.submit_all(make_trace(programs, occurrences=1,
                                 scale_index=scale_index))
    decide.run()

    def timed(factory):
        sched = factory()
        # inherit the decide pass's profiled single-stream anchors: a
        # long-lived serving process carries these, and without them
        # every bucket would re-anchor (a measured run + a pool drain in
        # the engine) inside the timed steady state
        sched._t_single.update(decide._t_single)
        sched._feats.update(decide._feats)
        best = float("inf")
        # one scheduler across reps: the first rep absorbs per-(bucket,
        # config) warmups, later reps are pure steady state — min wall
        # is the steady-state trace time, same protocol for both engines.
        # telemetry resets per rep so the recorded summary describes ONE
        # trace pass (matching n_requests), not the sum of all reps
        for _ in range(reps):
            sched.telemetry = TelemetryLog()
            sched.submit_all(trace())
            t0 = time.perf_counter()
            sched.run()
            best = min(best, time.perf_counter() - t0)
        return best, sched

    serial_wall, serial = timed(
        lambda: AdaptiveScheduler(OverlapHeuristicModel(),
                                  **sched_kwargs()))
    serial_rps = n_requests / serial_wall
    rows.append(f"serve_concurrent.serial.{backend},"
                f"{serial_wall/n_requests*1e6:.0f},"
                f"wall_ms={serial_wall*1e3:.1f},rps={serial_rps:.1f}")

    conc_wall, engine = timed(
        lambda: ConcurrentScheduler(OverlapHeuristicModel(), window=window,
                                    workers=workers, **sched_kwargs()))
    conc_rps = n_requests / conc_wall
    speedup = serial_wall / max(conc_wall, 1e-12)

    capacity = _parallel_capacity(programs, scale_index, workers)
    rows.append(f"serve_concurrent.window{window}.{backend},"
                f"{conc_wall/n_requests*1e6:.0f},"
                f"wall_ms={conc_wall*1e3:.1f},rps={conc_rps:.1f},"
                f"ctx_reuses={engine.stats['ctx_reuses']},"
                f"speedup={speedup:.3f}x")
    rows.append(f"serve_concurrent.capacity.{workers}threads,"
                f"{0:.0f},scaling={capacity:.3f}x,"
                f"capacity_fraction={speedup/max(capacity, 1e-12):.3f}")

    payload = {
        "programs": programs,
        "n_requests": n_requests,
        "backend": backend,
        "window": window,
        "workers": workers,
        "scale_index": scale_index,
        "reps": reps,
        "cpu_count": os.cpu_count(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "serial": {"wall_s": serial_wall, "throughput_rps": serial_rps,
                   **serial.telemetry.summary()},
        "concurrent": {"wall_s": conc_wall, "throughput_rps": conc_rps,
                       "ctx_reuses": int(engine.stats["ctx_reuses"]),
                       **engine.telemetry.summary()},
        "speedup": speedup,
        "parallel_capacity": capacity,
        "capacity_fraction": speedup / max(capacity, 1e-12),
    }
    os.makedirs(os.path.dirname(os.path.abspath(json_path)), exist_ok=True)
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=1)
    rows.append(f"# serving benchmark JSON written to {json_path}")
    return rows


FLEET_PROGRAMS = ["vecadd", "dotprod", "mvmult"]


def serve_fleet(programs=None, *, n_workers: int = 4, n_requests: int = 24,
                window: int = 2, backend: str = "host-sync",
                scale_index: int = 4, tenants: int = 8, reps: int = 3,
                kill_drill: bool = True,
                json_path: str = "BENCH_fleet.json") -> list[str]:
    """Fleet throughput scaling: the tenant-sharding router over 1..N
    worker PROCESSES on the same mixed multi-tenant trace, plus a
    SIGKILL drill proving worker death never loses a request.

    Fairness protocol (mirrors ``--serve-concurrent``):
      * one intra-op XLA thread per process (env set at module import) —
        process count is the only concurrency axis;
      * per worker-count, a fresh fleet serves one untimed warmup pass
        (spawn + compile + cold tunes) and then ``reps`` timed passes;
        min wall is the steady-state number;
      * the raw N-process speedup is normalized by the SAME run's
        measured parallel-capacity ceiling (the trace's own kernels
        issued from ``n_workers`` threads) — on a 1-2 vCPU CI box the
        physics caps scaling near 1x, and ``fleet_scaling_fraction``
        (speedup / min(N, ceiling)) is what the regression gate judges,
        not the host's core count.

    Besides throughput, every worker count reports end-to-end request
    latency percentiles (p50/p95/p99 over all timed passes) and the
    data-plane's ``ipc_overhead_fraction`` — the share of the best
    pass's router wall NOT covered by the busiest worker's engine wall,
    i.e. what dispatch, pickling, and collection cost; lower is better
    and CI gates it.

    This process starts no JAX backend (each worker owns a chip), so
    the capacity probe runs first, in a child process of its own that
    exits before any worker starts.

    The kill drill reuses the max-N fleet: SIGKILL one worker mid-trace,
    assert the router respawns the slot, requeues the un-acked work, and
    every admitted request still reaches a terminal status —
    ``fleet_kill_lost_requests`` has an exact-zero baseline.  Results
    land in ``BENCH_fleet.json``.
    """
    from repro.serving import latency_stats, make_trace
    from repro.serving.fleet import FleetRouter, WorkerConfig, shard_for

    programs = programs or FLEET_PROGRAMS
    occurrences = -(-n_requests // len(programs))

    def trace():
        return make_trace(programs, occurrences=occurrences,
                          tenants=tenants, scale_index=scale_index
                          )[:n_requests]

    counts = sorted({n for n in (1, 2, 4) if n <= n_workers} | {n_workers})
    with concurrent.futures.ProcessPoolExecutor(
            1, mp_context=multiprocessing.get_context("spawn")) as probe:
        capacity = probe.submit(_parallel_capacity, programs, scale_index,
                                n_workers).result()
    rows, walls, crashes = [], {}, 0
    latency, ipc = {}, {}
    router = None
    try:
        for n in counts:
            router = FleetRouter(
                n, worker=WorkerConfig(window=window, backend=backend,
                                       model="heuristic"))
            router.start()
            router.submit_all(trace())     # warmup: compile + cold tunes
            router.run()
            best, best_ipc, lats = float("inf"), None, []
            for _ in range(reps):
                reqs = trace()
                router.submit_all(reqs)
                t0 = time.perf_counter()
                results = router.run()
                wall = time.perf_counter() - t0
                lats.extend(r["sample"]["latency_s"] for r in results
                            if r["sample"].get("latency_s") is not None)
                if wall < best:
                    best = wall
                    best_ipc = router.last_run.get("ipc_overhead_fraction")
            walls[n] = best
            ipc[n] = best_ipc
            # end-to-end request latency (enqueue -> retire) across all
            # timed passes; perf_counter stamps are comparable across
            # router and workers (CLOCK_MONOTONIC process-agnostic)
            lstats = latency_stats(lats)
            latency[n] = {
                "p50_ms": lstats["p50_s"] * 1e3 if lstats else None,
                "p95_ms": lstats["p95_s"] * 1e3 if lstats else None,
                "p99_ms": lstats["p99_s"] * 1e3 if lstats else None,
            }
            crashes += router.stats.get("worker_deaths", 0) \
                - router.stats.get("injected_kills", 0)
            ipc_s = (f",ipc={best_ipc:.3f}" if best_ipc is not None else "")
            lat_s = ("" if lstats is None else
                     f",p50_ms={latency[n]['p50_ms']:.1f}"
                     f",p99_ms={latency[n]['p99_ms']:.1f}")
            rows.append(f"serve_fleet.workers{n}.{backend},"
                        f"{best/n_requests*1e6:.0f},"
                        f"wall_ms={best*1e3:.1f},"
                        f"rps={n_requests/best:.1f},"
                        f"speedup={walls[1]/best:.3f}x"
                        + lat_s + ipc_s)
            if n != n_workers:
                router.close()
                router = None

        speedup = walls[1] / max(walls[n_workers], 1e-12)
        ceiling = min(float(n_workers), max(1.0, capacity))
        scaling_fraction = speedup / ceiling
        rows.append(f"serve_fleet.capacity.{n_workers}procs,0,"
                    f"scaling={capacity:.3f}x,ceiling={ceiling:.3f},"
                    f"scaling_fraction={scaling_fraction:.3f}")

        kill = None
        if kill_drill and router is not None:
            # reuse the warm max-N fleet; kill the worker that owns
            # tenant-0 once a quarter of the trace has retired
            victim = shard_for("tenant-0", n_workers)
            base_deaths = router.stats.get("worker_deaths", 0)
            reqs = trace()
            router.submit_all(reqs)
            router.inject_kill(victim, after_results=max(1, n_requests // 4))
            results = router.run()
            terminal = sum(r["status"] in ("served", "degraded", "failed",
                                           "timeout") for r in results)
            kill = {
                "victim_slot": victim,
                "results": len(results),
                "terminal": terminal,
                "deaths": router.stats.get("worker_deaths", 0) - base_deaths,
                "respawns": router.stats.get("worker_respawns", 0),
                "requeued": router.stats.get("requeued_requests", 0),
                "duplicates": router.stats.get("duplicate_results", 0),
            }
            rows.append(f"serve_fleet.kill_drill.slot{victim},0,"
                        f"deaths={kill['deaths']},"
                        f"respawns={kill['respawns']},"
                        f"requeued={kill['requeued']},"
                        f"terminal={terminal}/{n_requests}")
    finally:
        if router is not None:
            router.close()
    fleet_summary = router.summary() if router is not None else {}

    payload = {
        "programs": programs,
        "n_requests": n_requests,
        "n_workers": n_workers,
        "window": window,
        "backend": backend,
        "scale_index": scale_index,
        "tenants": tenants,
        "reps": reps,
        "cpu_count": os.cpu_count(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "walls_s": {str(n): walls[n] for n in counts},
        "throughput_rps": {str(n): n_requests / walls[n] for n in counts},
        "latency_by_workers": {str(n): latency[n] for n in counts},
        "ipc_overhead_fraction_by_workers": {str(n): ipc[n] for n in counts},
        "fleet_speedup": speedup,
        "parallel_capacity": capacity,
        "capacity_ceiling": ceiling,
        # -- gated --
        "ipc_overhead_fraction": ipc.get(n_workers),
        "fleet_scaling_fraction": scaling_fraction,
        "fleet_worker_crashes": crashes,
        "fleet_kill_lost_requests": (n_requests - kill["results"]
                                     if kill else None),
        "fleet_kill_terminal_fraction": (kill["terminal"] / n_requests
                                         if kill else None),
        "kill_drill": kill,
        "fleet": {k: v for k, v in fleet_summary.items()
                  if k != "metrics"},
    }
    os.makedirs(os.path.dirname(os.path.abspath(json_path)), exist_ok=True)
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=1)
    rows.append(f"# fleet benchmark JSON written to {json_path}")
    return rows


SERVE_ORACLE_PROGRAMS = ["vecadd", "dotprod", "mvmult", "binomial"]
# the regret protocol's shared candidate space: small enough to profile
# exhaustively (the oracle side), identical for the adaptive scheduler
# (the achieved side) — regret compares picks over the SAME choices
ORACLE_GRID = [StreamConfig(p, t) for p in (1, 2, 4)
               for t in (1, 2, 4, 8, 16) if t >= p]


def serve_oracle_trace(programs=None, *, tenants: int = 3, rounds: int = 12,
                       backend: str = "host-sync", window: int = 4,
                       workers: int | None = None, scale_index: int = 8,
                       oracle_reps: int = 3,
                       json_path: str = "BENCH_oracle.json") -> list[str]:
    """Long-trace oracle-regret benchmark: the adaptive engine's
    steady state vs a theoretically perfect predictor, per tenant.

    The paper's headline claim is that the learnt predictor delivers
    over 93% of the oracle's performance.  This measures our *serving
    loop* against the same bar:

      oracle    exhaustively profile ``ORACLE_GRID`` per workload
                bucket — the perfect predictor's pick and its runtime.
                The grid is profiled TWICE, before and after serving,
                and merged min-per-config: a neighbor-load spike during
                either pass then cannot masquerade as (or hide) regret
                on this shared-vCPU class of CI box;
      achieved  serve a ``rounds``-round multi-tenant trace through the
                concurrent engine with tenant isolation and load-aware
                drift, then read each tenant's steady-state cache entry
                (the config its NEXT request would use) and look its
                idle runtime up in the same profiled grid;
      regret    oracle_runtime / achieved_runtime per (tenant,
                workload), in (0, 1]; reported per tenant and overall.

    Reading achieved runtimes from the same idle-profiled grid keeps
    contention out of the *metric* (the engine still serves under
    contention — that is what the load-aware drift signal is being
    scored on: spurious refinements are also reported).
    """
    from repro.core.autotuner import TuningCache
    from repro.serving import (ConcurrentScheduler, DriftDetector,
                               OverlapHeuristicModel, Refiner,
                               TelemetryLog, make_trace)

    programs = programs or SERVE_ORACLE_PROGRAMS
    workers = workers or max(2, min(window, os.cpu_count() or 2))
    tenant_names = [f"tenant-{i}" for i in range(tenants)]
    rows = []

    # --- oracle pass A: exhaustive profiling per workload bucket ---------
    trace = make_trace(programs, occurrences=rounds, tenants=tenant_names,
                       scale_index=scale_index)
    first = {}
    for req in trace:
        first.setdefault(req.workload, req)
    runners = {name: StreamedRunner(get_workload(name), req.chunked,
                                    req.shared, backend=backend)
               for name, req in first.items()}
    grids = {}           # workload -> {cfg: min wall over both passes}
    for name, runner in runners.items():
        n_rows = next(iter(runner.chunked.values())).shape[0]
        cands = [c for c in ORACLE_GRID
                 if c.partitions * c.tasks <= n_rows]
        grids[name] = profile_grid_interleaved(runner, cands,
                                                sweeps=oracle_reps)

    # --- achieved: isolated multi-tenant adaptive serving ----------------
    model = OverlapHeuristicModel()
    cache = TuningCache()
    sched = ConcurrentScheduler(
        model, window=window, workers=workers,
        backend=backend, policy="fair", cache=cache,
        candidates=list(ORACLE_GRID), isolate_tenants=True,
        drift=DriftDetector(window=8, threshold=0.35, min_samples=2,
                            cooldown=2),
        refiner=Refiner(model, cache, candidates=list(ORACLE_GRID),
                        top_k=3, reps=3),
        telemetry=TelemetryLog(), keep_outputs=False)
    with sched:
        sched.submit_all(trace)
        t0 = time.perf_counter()
        sched.run()
        wall = time.perf_counter() - t0

        # --- oracle pass B + min-merge ----------------------------------
        oracle = {}      # workload -> (best cfg, t_s, merged grid)
        for name, runner in runners.items():
            merged = profile_grid_interleaved(
                runner, list(grids[name]), sweeps=oracle_reps,
                prior=grids[name])
            best = min(merged, key=merged.get)
            oracle[name] = (best, merged[best], merged)
            rows.append(f"serve_oracle.oracle.{name},"
                        f"{merged[best]*1e6:.0f},"
                        f"config={best.partitions}x{best.tasks}")

        # steady state: the cache entry each (tenant, workload) would
        # serve its NEXT request from, scored on the idle-profiled grid
        per_tenant = {}
        for tenant in tenant_names:
            ctx = sched.tenancy.get(tenant)
            per_workload = {}
            for name, req in first.items():
                key = sched.cache.key(name, req.chunked, req.shared,
                                      backend, sched.model_tag,
                                      namespace=ctx.namespace)
                entry = sched.cache.get(key)
                if entry is None:        # tenant never saw this workload
                    continue
                _, t_oracle, measured = oracle[name]
                achieved = measured.get(entry.config)
                if achieved is None:     # off-grid (cannot happen today)
                    achieved = StreamedRunner(
                        get_workload(name), req.chunked, req.shared,
                        backend=backend).run(entry.config,
                                             reps=oracle_reps)
                per_workload[name] = {
                    "config": entry.config.as_tuple(),
                    "source": entry.source,
                    "achieved_s": achieved,
                    "oracle_s": t_oracle,
                    "regret": t_oracle / max(achieved, 1e-12),
                }
            regrets = [w["regret"] for w in per_workload.values()]
            regret = sum(regrets) / len(regrets) if regrets else None
            per_tenant[tenant] = {
                "regret": regret,
                "refinements": ctx.refinements,
                "served": ctx.served,
                "per_workload": per_workload,
            }
            regret_str = f"{regret:.3f}" if regret is not None else "n/a"
            rows.append(
                f"serve_oracle.{tenant},0,regret={regret_str},"
                f"refinements={ctx.refinements},served={ctx.served}")

        all_regrets = [t["regret"] for t in per_tenant.values()
                       if t["regret"] is not None]
        # a tenant can go unserved when the trace is shorter than the
        # tenant count (tiny smoke configs) — regret is then undefined
        mean_regret = (sum(all_regrets) / len(all_regrets)
                       if all_regrets else None)
        summary = sched.telemetry.summary()
        mean_str = (f"{mean_regret:.3f}" if mean_regret is not None
                    else "n/a")
        rows.append(f"serve_oracle.mean,0,regret={mean_str},"
                    f"target=0.93,refinements={summary['refinements']},"
                    f"requests={summary['requests']}")

        payload = {
            "programs": programs,
            "tenants": tenant_names,
            "rounds": rounds,
            "n_requests": len(trace),
            "backend": backend,
            "window": window,
            "workers": workers,
            "scale_index": scale_index,
            "oracle_reps": oracle_reps,
            "cpu_count": os.cpu_count(),
            "wall_s": wall,
            "oracle": {name: {"config": cfg.as_tuple(), "t_s": t}
                       for name, (cfg, t, _) in oracle.items()},
            "per_tenant": per_tenant,
            "mean_regret": mean_regret,
            "target_regret": 0.93,
            "parallel_capacity": sched.parallel_capacity,
            "telemetry_summary": summary,
        }
    os.makedirs(os.path.dirname(os.path.abspath(json_path)), exist_ok=True)
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=1)
    rows.append(f"# oracle-regret JSON written to {json_path}")
    return rows


TRACE_POLICIES = ("fifo", "priority", "fair", "deadline")


def serve_latency_trace(*, n_requests: int = 100_000, seed: int = 0,
                        window: int = 8, capacity: float = 1.6,
                        json_path: str = "BENCH_latency.json") -> list[str]:
    """Tail-latency trace replay: every queue policy on the SAME seeded
    bursty million-scale trace, in virtual time.

    Uses :mod:`repro.serving.traces`: a deterministic MMPP/Zipf trace
    over the registered workload suite is replayed through the real
    request queue + drift detector on a virtual clock, so a 10^5-request
    run takes seconds and the p50/p95/p99 latencies, SLO-violation
    rates, shed counts, and queue-depth stats are exactly reproducible
    — the regression gate can hold them to tight tolerances because no
    wall-clock noise enters the numbers.

    Two extra runs pin the drift detector's long-trace behaviour:
      * a stationary Poisson trace at the same window must produce ZERO
        refinements (contention at window=8 must not masquerade as
        drift — the load-aware signal's acceptance bar);
      * the bursty ``deadline`` run must beat ``fifo`` on SLO-violation
        rate (EDF boost + shedding earning their keep).
    """
    from repro.serving.traces import (TraceConfig, generate_trace,
                                      simulate_trace)

    rows = []
    reports = {}
    bursty = TraceConfig(n_requests=n_requests, seed=seed, arrival="bursty")
    for policy in TRACE_POLICIES:
        r = simulate_trace(generate_trace(bursty), policy=policy,
                           window=window, capacity=capacity, seed=seed)
        reports[policy] = r
        lat, slo, qd = r["latency"], r["slo"], r["queue_depth"]
        rows.append(
            f"serve_trace.bursty.{policy},{lat['p95_s']*1e6:.0f},"
            f"p50_ms={lat['p50_s']*1e3:.2f},p99_ms={lat['p99_s']*1e3:.2f},"
            f"viol_rate={slo['violation_rate']:.4f},shed={slo['shed']},"
            f"depth_p95={qd['p95']},refinements={r['refinements']}")

    stationary = simulate_trace(
        generate_trace(TraceConfig(n_requests=n_requests, seed=seed + 1,
                                   arrival="poisson")),
        policy="fifo", window=window, capacity=capacity, seed=seed + 1)
    rows.append(
        f"serve_trace.stationary.fifo,"
        f"{stationary['latency']['p95_s']*1e6:.0f},"
        f"refinements={stationary['refinements']},"
        f"viol_rate={stationary['slo']['violation_rate']:.4f}")

    fifo_rate = reports["fifo"]["slo"]["violation_rate"]
    dl_rate = reports["deadline"]["slo"]["violation_rate"]
    payload = {
        "n_requests": n_requests,
        "seed": seed,
        "window": window,
        "capacity": capacity,
        "arrival": "bursty",
        "policies": reports,
        "stationary": stationary,
        # gated, lower is better (deterministic virtual-time numbers)
        "deadline_slo_violation_rate": dl_rate,
        "fifo_slo_violation_rate": fifo_rate,
        "deadline_p95_latency_ms":
            reports["deadline"]["latency"]["p95_s"] * 1e3,
        "stationary_refinements": stationary["refinements"],
        # gated, higher is better: how much EDF+shedding beats FIFO
        "deadline_vs_fifo_violation_improvement":
            fifo_rate / max(dl_rate, 1e-9),
    }
    os.makedirs(os.path.dirname(os.path.abspath(json_path)), exist_ok=True)
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=1)
    rows.append(f"# latency-trace JSON written to {json_path}")
    return rows


REAL_TRACE_PROGRAMS = ["vecadd", "dotprod", "mvmult"]


def serve_real_trace(*, n_requests: int = 10_000, seed: int = 0,
                     window: int = 8, workers: int | None = None,
                     scale_index: int = 0, backend: str = "host-sync",
                     profile_alloc: bool = False,
                     alloc_requests: int = 2_000,
                     chrome_trace: str | None = None,
                     metrics_out: str | None = None,
                     json_path: str = "BENCH_overhead.json") -> list[str]:
    """Real-engine hot-path profiling: replay a generated 10^4-request
    trace through the real :class:`ConcurrentScheduler` — kernels
    executing, wall clock — with span tracing and the metrics registry
    live, and attribute where the time went.

    This is ROADMAP's real-engine-replay item: the virtual-time harness
    (``--serve-trace``) answers tail-latency questions at 10^5+ scale,
    but only a wall-clock run exposes the *scheduler's own* overheads —
    coordinator Python time per decision, retire-path bookkeeping,
    hot-path allocations.  The trace reuses :func:`generate_trace`
    (seeded Poisson arrivals, Zipf workload/tenant skew) restricted to a
    small program set at one scale, with virtual-epoch arrival stamps
    cleared so the engine re-stamps them on its own clock.

    Reported to ``BENCH_overhead.json``:

      * per-stage wall attribution (decide/tune/dispatch/retire/refine)
        from top-level spans;
      * ``kernel_exec_s`` (sum of measured kernel walls) vs
        ``wall_s`` — and ``python_overhead_fraction``: coordinator
        decide+retire wall over total wall, the gated metric (a
        same-run ratio, so host drift largely cancels);
      * with ``--profile-alloc``, top allocation sites from a separate,
        shorter tracemalloc'd pass (tracemalloc ~doubles allocation
        cost, so the timed pass runs untraced).
    """
    from repro.serving import (ConcurrentScheduler, DriftDetector,
                               HotPathProfiler, MetricsRegistry,
                               OverlapHeuristicModel, TelemetryLog,
                               Tracer)
    from repro.serving.traces import TraceConfig, generate_trace

    workers = workers or max(2, min(window, os.cpu_count() or 2))
    cfg = TraceConfig(
        n_requests=n_requests, seed=seed, arrival="poisson",
        workloads=tuple(REAL_TRACE_PROGRAMS),
        scale_indices=(scale_index,), churn_prob=0.0,
        slo_choices=None)

    def requests():
        reqs = list(generate_trace(cfg))
        for r in reqs:
            # generated stamps live on the virtual trace epoch; the real
            # engine's clock is perf_counter — submit() re-stamps
            r.arrival_s = None
        return reqs

    def build(tracer, metrics):
        # a storm-proof drift threshold: refinements re-profile on a
        # quiesced pool and would benchmark the refiner, not the
        # serving hot path
        return ConcurrentScheduler(
            OverlapHeuristicModel(), window=window, workers=workers,
            backend=backend, drift=DriftDetector(threshold=1e9),
            telemetry=TelemetryLog(), keep_outputs=False,
            tracer=tracer, metrics=metrics)

    rows = []
    tracer = Tracer()
    metrics = MetricsRegistry()
    sched = build(tracer, metrics)
    with sched:
        sched.submit_all(requests())
        prof = HotPathProfiler(tracer)
        with prof:
            results = sched.run()
        report = prof.report()

    wall = report["wall_s"]
    stages = report["stages"]
    kernel_exec_s = sum(r.measured_s for r in results)
    coord_s = stages["decide"]["wall_s"] + stages["retire"]["wall_s"]
    overhead_fraction = coord_s / max(wall, 1e-12)
    rps = len(results) / max(wall, 1e-12)

    rows.append(f"serve_real.window{window}.{backend},"
                f"{wall / max(len(results), 1) * 1e6:.0f},"
                f"requests={len(results)},wall_s={wall:.2f},"
                f"rps={rps:.1f},"
                f"python_overhead_fraction={overhead_fraction:.4f}")
    for stage in ("decide", "tune", "dispatch", "retire", "refine"):
        st = stages[stage]
        mean_us = (st["mean_s"] * 1e6) if st["mean_s"] is not None else 0
        rows.append(f"serve_real.stage.{stage},{mean_us:.0f},"
                    f"wall_s={st['wall_s']:.3f},count={st['count']}")

    allocations = None
    if profile_alloc:
        # separate pass: tracemalloc roughly doubles allocation cost, so
        # the timed numbers above stay clean and this one stays short
        n_alloc = min(alloc_requests, n_requests)
        alloc_cfg = dataclasses.replace(cfg, n_requests=n_alloc)
        tracer2 = Tracer()
        sched2 = build(tracer2, MetricsRegistry())
        with sched2:
            reqs = list(generate_trace(alloc_cfg))
            for r in reqs:
                r.arrival_s = None
            sched2.submit_all(reqs)
            prof2 = HotPathProfiler(tracer2, alloc=True)
            with prof2:
                sched2.run()
        allocations = prof2.report()["allocations"]
        for a in allocations[:5]:
            site = a["site"]
            if len(site) > 72:
                site = "..." + site[-69:]
            rows.append(f"serve_real.alloc,0,site={site},"
                        f"kb={a['size_kb']:.0f},count={a['count']}")

    if chrome_trace:
        n_spans = tracer.export_chrome(chrome_trace)
        rows.append(f"# chrome trace ({n_spans} spans) written to "
                    f"{chrome_trace}")
    if metrics_out:
        metrics.save(metrics_out)
        rows.append(f"# metrics snapshot written to {metrics_out}")

    payload = {
        "programs": REAL_TRACE_PROGRAMS,
        "n_requests": len(results),
        "seed": seed,
        "backend": backend,
        "window": window,
        "workers": workers,
        "scale_index": scale_index,
        "cpu_count": os.cpu_count(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "wall_s": wall,
        "cpu_s": report["cpu_s"],
        "throughput_rps": rps,
        "per_stage_s": stages,
        "kernel_exec_s": kernel_exec_s,
        "dispatch_overhead_s": stages["dispatch"]["wall_s"]
                               - kernel_exec_s,
        "coordinator_s": coord_s,
        "python_overhead_fraction": overhead_fraction,
        "telemetry_summary": sched.telemetry.summary(),
        "metrics": metrics.snapshot(),
    }
    if allocations is not None:
        payload["allocations"] = allocations
    os.makedirs(os.path.dirname(os.path.abspath(json_path)), exist_ok=True)
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=1)
    rows.append(f"# overhead JSON written to {json_path}")
    return rows


DEFAULT_FAULT_SCHEDULE = os.path.join(ROOT, "benchmarks", "data",
                                      "chaos_faults.json")


def serve_chaos(*, n_requests: int = 400, seed: int = 0, window: int = 8,
                workers: int | None = None, scale_index: int = 0,
                backend: str = "host-threads",
                fault_schedule: str = DEFAULT_FAULT_SCHEDULE,
                watchdog_s: float = 0.25, slo_margin: float = 2.0,
                slo_floor_s: float = 0.25,
                json_path: str = "BENCH_resilience.json") -> list[str]:
    """Chaos benchmark: the PR 6 bursty trace through the REAL concurrent
    engine twice — fault-free, then under the committed fault schedule —
    with the resilience layer live in both runs.

    Measures what the fault-tolerance layer actually buys (ROADMAP's
    robustness item): the chaos run must complete with **zero scheduler
    crashes** and every request terminal (served / degraded / failed /
    timeout — never lost), and the report splits *degraded* (answered
    via a fallback rung) from *failed* so graceful degradation is
    distinguishable from dropped work.

    SLO accounting: request ``i``'s deadline is
    ``slo_floor_s + slo_margin * (its own fault-free latency)`` — a
    per-request yardstick from the baseline run, so the fault-free
    violation rate is 0 by construction and
    ``chaos_slo_violation_delta`` *is* the latency damage the faults
    caused (gated; the committed schedule bounds how much a retry storm
    or breaker window may cost).  Breaker open→closed transitions give
    ``mean_recovery_s``.

    Gated in ``BENCH_resilience.json``: ``chaos_crashes`` (exact-zero),
    ``chaos_terminal_fraction`` (higher), ``chaos_failed_fraction``
    (lower), ``chaos_slo_violation_delta`` (lower).
    """
    import collections

    from repro.serving import (BreakerConfig, ConcurrentScheduler,
                               DriftDetector, FaultPlan, MetricsRegistry,
                               OverlapHeuristicModel, ResiliencePolicy,
                               TelemetryLog)
    from repro.serving.traces import TraceConfig, generate_trace

    workers = workers or max(2, min(window, os.cpu_count() or 2))
    # two scales + a churn trickle: the nearest-bucket rung needs a
    # neighboring shape bucket in the cache to borrow from
    cfg = TraceConfig(
        n_requests=n_requests, seed=seed, arrival="bursty",
        workloads=tuple(REAL_TRACE_PROGRAMS),
        scale_indices=(scale_index, scale_index + 1), churn_prob=0.05,
        slo_choices=None)
    # breaker cooldown scaled to the run: the committed outage window
    # spans a few hundred ms of wall, and recovery (open -> half-open
    # probe -> closed) must happen INSIDE the measured run
    policy = ResiliencePolicy(
        breaker=BreakerConfig(k=3, cooldown_s=0.3), watchdog_s=watchdog_s)

    def run_once(faults, deadline_offsets):
        # fresh requests every run: the engine mutates arrival stamps
        reqs = list(generate_trace(cfg))
        for r in reqs:
            r.arrival_s = None
            r.deadline_s = None
        metrics = MetricsRegistry()
        sched = ConcurrentScheduler(
            OverlapHeuristicModel(), window=window, workers=workers,
            backend=backend, drift=DriftDetector(threshold=1e9),
            telemetry=TelemetryLog(), keep_outputs=False,
            metrics=metrics, faults=faults, resilience=policy)
        with sched:
            sched.submit_all(reqs)      # stamps arrival_s on the real clock
            if deadline_offsets is not None:
                for r, off in zip(reqs, deadline_offsets):
                    r.deadline_s = r.arrival_s + off
            t0 = time.perf_counter()
            results = sched.run()
            wall = time.perf_counter() - t0
        return sched, metrics, results, wall

    rows = []

    # -- jit warmup: first-compile walls (100s of ms) would otherwise
    # read as watchdog timeouts and poison the per-request SLO yardstick
    run_once(None, None)

    # -- baseline: resilience live, no faults --------------------------------
    _, _, base_results, base_wall = run_once(None, None)
    base_lat = [r.sample.latency_s for r in base_results]
    offsets = [slo_floor_s + slo_margin * (lat if lat is not None else 0.0)
               for lat in base_lat]
    base_viol = sum(1 for lat, off in zip(base_lat, offsets)
                    if lat is None or lat > off)
    base_rate = base_viol / max(len(base_results), 1)
    rows.append(f"serve_chaos.baseline,"
                f"{base_wall / max(len(base_results), 1) * 1e6:.0f},"
                f"requests={len(base_results)},wall_s={base_wall:.2f},"
                f"slo_violation_rate={base_rate:.4f}")

    # -- chaos: same engine, same policy, committed fault schedule -----------
    faults = FaultPlan.load(fault_schedule)
    crashes = 0
    try:
        sched, metrics, results, wall = run_once(faults, offsets)
    except BaseException as e:  # noqa: BLE001 — a crash IS the measurement
        crashes = 1
        rows.append(f"serve_chaos.CRASH,0,error={type(e).__name__}: {e}")
        sched = metrics = None
        results, wall = [], 0.0

    statuses = collections.Counter(r.status for r in results)
    n_terminal = len(results)
    terminal_fraction = n_terminal / max(n_requests, 1)
    failed = statuses["failed"] + statuses["timeout"]
    failed_fraction = failed / max(n_requests, 1)
    degraded_fraction = statuses["degraded"] / max(n_requests, 1)
    chaos_viol = sum(
        1 for r in results
        if r.status in ("failed", "timeout") or (
            r.sample.latency_s is not None
            and r.sample.deadline_s is not None
            and r.sample.t_retire_s is not None
            and r.sample.t_retire_s > r.sample.deadline_s))
    chaos_rate = chaos_viol / max(n_terminal, 1)
    slo_delta = max(0.0, chaos_rate - base_rate)

    recoveries = []
    if sched is not None:
        opened_at: dict = {}
        for t, key, state in sched.breaker.events:
            if state == "open":
                opened_at.setdefault(key, t)
            elif state == "closed" and key in opened_at:
                recoveries.append(t - opened_at.pop(key))
    mean_recovery_s = (sum(recoveries) / len(recoveries)
                       if recoveries else None)

    stats = dict(sched.stats) if sched is not None else {}

    def counter_total(name):
        snap = metrics.snapshot() if metrics is not None else {}
        return sum(v["value"] for v in snap.get(name, {}).get("values", []))

    recovered = counter_total("serving.faults.recovered")
    rows.append(f"serve_chaos.window{window}.{backend},"
                f"{wall / max(n_terminal, 1) * 1e6:.0f},"
                f"requests={n_terminal}/{n_requests},wall_s={wall:.2f},"
                f"crashes={crashes},"
                f"faults_injected={faults.fired}")
    rows.append(f"serve_chaos.outcomes,0,"
                f"served={statuses['served']},"
                f"degraded={statuses['degraded']},"
                f"failed={statuses['failed']},"
                f"timeout={statuses['timeout']},"
                f"recovered={recovered},"
                f"watchdog_fired={stats.get('watchdog_fired', 0)}")
    rows.append(f"serve_chaos.slo,0,"
                f"base_rate={base_rate:.4f},chaos_rate={chaos_rate:.4f},"
                f"delta={slo_delta:.4f},"
                f"breaker_recoveries={len(recoveries)},"
                f"mean_recovery_s="
                f"{mean_recovery_s if mean_recovery_s is None else round(mean_recovery_s, 3)}")

    payload = {
        "programs": REAL_TRACE_PROGRAMS,
        "n_requests": n_requests,
        "seed": seed,
        "backend": backend,
        "window": window,
        "workers": workers,
        "scale_index": scale_index,
        "watchdog_s": watchdog_s,
        "fault_schedule": os.path.relpath(fault_schedule, ROOT),
        "fault_plan": faults.to_json(),
        "faults_injected": faults.fired,
        "cpu_count": os.cpu_count(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "baseline_wall_s": base_wall,
        "chaos_wall_s": wall,
        "statuses": dict(statuses),
        "stats": stats,
        "chaos_crashes": crashes,
        "chaos_recovered": recovered,
        "chaos_terminal_fraction": terminal_fraction,
        "chaos_failed_fraction": failed_fraction,
        "chaos_degraded_fraction": degraded_fraction,
        "base_slo_violation_rate": base_rate,
        "chaos_slo_violation_rate": chaos_rate,
        "chaos_slo_violation_delta": slo_delta,
        "breaker_recoveries": len(recoveries),
        "mean_recovery_s": mean_recovery_s,
        "metrics": metrics.snapshot() if metrics is not None else {},
        "telemetry_summary": (sched.telemetry.summary()
                              if sched is not None else None),
    }
    os.makedirs(os.path.dirname(os.path.abspath(json_path)), exist_ok=True)
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=1)
    rows.append(f"# resilience JSON written to {json_path}")
    return rows


def model_eval(programs=None, *, datasets: int = 2, reps: int = 1,
               epochs: int = 600,
               json_path: str = "BENCH_model.json") -> list[str]:
    """Leave-one-program-out model evaluation: the learnt MLP's achieved
    speedup vs the per-cell oracle AND vs the zero-training overlap
    heuristic on the SAME profiled corpus.

    This is the offline-model quality gate (the paper's §5.3.1 protocol
    on our corpus): ``model_frac_of_oracle`` tracks the headline
    "% of oracle" number, and ``model_vs_heuristic`` asserts the trained
    model actually beats the stand-in it replaced on the serving default
    path.  Both land in ``BENCH_model.json`` for
    ``check_regression.py``; profiling reuses (and extends) the persistent
    profile cache, which CI restores via ``actions/cache``."""
    from repro.core.modeling import OverlapHeuristicModel
    from repro.core.modeling.artifacts import corpus_fingerprint
    from repro.core.modeling.evaluate import evaluate_model, loo_evaluate
    from repro.launch.train_model import DEFAULT_TRAIN_PROGRAMS

    programs = programs or list(DEFAULT_TRAIN_PROGRAMS)
    samples = ds.generate(programs, datasets_per_program=datasets,
                          reps=reps, verbose=True)
    rows = []

    t0 = time.perf_counter()
    cv = loo_evaluate(samples, train_kwargs={"epochs": epochs},
                      verbose=True)
    t_cv = time.perf_counter() - t0
    heur = evaluate_model(OverlapHeuristicModel(), samples)

    for prog, r in sorted(cv["per_program"].items()):
        rows.append(f"model_eval.loo.{prog},0,"
                    f"achieved={r['achieved']:.3f}x,"
                    f"oracle={r['oracle']:.3f}x,"
                    f"pct_of_oracle={100 * r['frac_of_oracle']:.1f}")
    vs_heur = cv["mean_achieved"] / heur["mean_speedup"]
    rows.append(f"model_eval.mean,0,"
                f"model={cv['mean_achieved']:.3f}x,"
                f"heuristic={heur['mean_speedup']:.3f}x,"
                f"oracle={cv['mean_oracle']:.3f}x,"
                f"frac_of_oracle={cv['frac_of_oracle']:.3f},"
                f"vs_heuristic={vs_heur:.3f}x")

    payload = {
        "programs": programs,
        "datasets_per_program": datasets,
        "reps": reps,
        "epochs": epochs,
        "n_cells": cv["n_cells"],
        "corpus_fingerprint": corpus_fingerprint(samples),
        "cv_wall_s": t_cv,
        "model": cv,
        "heuristic": heur,
        "model_frac_of_oracle": cv["frac_of_oracle"],
        "heuristic_frac_of_oracle": heur["frac_of_oracle"],
        "model_vs_heuristic": vs_heur,
        "target_frac_of_oracle": 0.93,
    }
    os.makedirs(os.path.dirname(os.path.abspath(json_path)), exist_ok=True)
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=1)
    rows.append(f"# model-eval JSON written to {json_path}")
    return rows


def dryrun_summary() -> list[str]:
    rows = []
    for path in sorted(glob.glob(os.path.join(
            ROOT, "benchmarks", "data", "dryrun", "*.json"))):
        try:
            with open(path) as f:
                d = json.load(f)
        except (OSError, ValueError):
            continue
        if "roofline" not in d:
            continue
        r = d["roofline"]
        rows.append(
            f"dryrun.{d['arch']}.{d['shape']}."
            f"{'pod2' if 'pod' in d['mesh'] else 'pod1'},"
            f"{r['bound_s']*1e6:.0f},"
            f"dominant={r['dominant']},frac={r['roofline_fraction']:.4f}"
            if "bound_s" in r else
            f"dryrun.{d['arch']}.{d['shape']},"
            f"{max(r['compute_s'], r['memory_s'], r['collective_s'])*1e6:.0f},"
            f"dominant={r['dominant']},frac={r['roofline_fraction']:.4f}")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--programs", default=None)
    ap.add_argument("--datasets", type=int, default=3)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--compare-backends", action="store_true",
                    help="A/B every runner backend; skips the paper figures")
    ap.add_argument("--serve", action="store_true",
                    help="static-vs-adaptive serving trace; skips the "
                         "paper figures")
    ap.add_argument("--serve-requests", type=int, default=12)
    ap.add_argument("--serve-backend", default="host-sync")
    ap.add_argument("--serve-json", default=None,
                    help="write the serving comparison + telemetry JSON")
    ap.add_argument("--serve-concurrent", action="store_true",
                    help="serial-vs-concurrent engine throughput on a "
                         "long mixed trace; writes BENCH_serving.json")
    ap.add_argument("--serve-window", type=int, default=8,
                    help="concurrent engine in-flight window")
    ap.add_argument("--serve-workers", type=int, default=None)
    ap.add_argument("--serve-scale", type=int, default=8,
                    help="dataset scale index for the concurrent trace")
    ap.add_argument("--serve-trace", action="store_true",
                    help="virtual-time tail-latency trace replay over "
                         "every queue policy; writes BENCH_latency.json")
    ap.add_argument("--trace-requests", type=int, default=100_000,
                    help="requests per generated trace for --serve-trace")
    ap.add_argument("--trace-seed", type=int, default=0)
    ap.add_argument("--serve-real-trace", action="store_true",
                    help="replay a generated trace through the REAL "
                         "concurrent engine (kernels executing, wall "
                         "clock) with span tracing + metrics live; "
                         "writes BENCH_overhead.json")
    ap.add_argument("--real-trace-requests", type=int, default=10_000,
                    help="requests for --serve-real-trace")
    ap.add_argument("--real-trace-scale", type=int, default=0,
                    help="dataset scale index for --serve-real-trace")
    ap.add_argument("--profile-alloc", action="store_true",
                    help="--serve-real-trace: add a shorter tracemalloc "
                         "pass reporting top hot-path allocation sites")
    ap.add_argument("--chrome-trace", default=None,
                    help="--serve-real-trace: export the span trace as "
                         "Chrome trace-event JSON (Perfetto-loadable)")
    ap.add_argument("--metrics-out", default=None,
                    help="--serve-real-trace: save the metrics registry "
                         "snapshot JSON here")
    ap.add_argument("--serve-chaos", action="store_true",
                    help="fault-free vs fault-injected run of the real "
                         "engine with the resilience layer live; writes "
                         "BENCH_resilience.json")
    ap.add_argument("--chaos-requests", type=int, default=400,
                    help="requests per run for --serve-chaos")
    ap.add_argument("--chaos-backend", default="host-threads",
                    help="--serve-chaos primary backend (must differ "
                         "from host-sync for the dispatch-fallback rung "
                         "to be exercised)")
    ap.add_argument("--fault-schedule", default=DEFAULT_FAULT_SCHEDULE,
                    help="--serve-chaos: committed FaultPlan JSON")
    ap.add_argument("--chaos-watchdog-ms", type=float, default=250.0,
                    help="--serve-chaos execution watchdog (ms)")
    ap.add_argument("--serve-fleet", action="store_true",
                    help="fleet throughput scaling: tenant-sharded "
                         "router over 1..N worker processes + SIGKILL "
                         "respawn drill -> BENCH_fleet.json")
    ap.add_argument("--fleet-workers", type=int, default=4,
                    help="max worker-process count for --serve-fleet")
    ap.add_argument("--fleet-requests", type=int, default=24,
                    help="requests per trace pass for --serve-fleet")
    ap.add_argument("--fleet-window", type=int, default=2,
                    help="per-worker engine window for --serve-fleet")
    ap.add_argument("--fleet-scale", type=int, default=4,
                    help="dataset scale index for --serve-fleet")
    ap.add_argument("--fleet-reps", type=int, default=3,
                    help="timed passes per worker count (min wall wins)")
    ap.add_argument("--fleet-tenants", type=int, default=8,
                    help="tenant count for --serve-fleet (8 spreads "
                         "evenly over 2 and 4 shards)")
    ap.add_argument("--no-kill-drill", action="store_true",
                    help="skip the --serve-fleet SIGKILL respawn drill")
    ap.add_argument("--serve-oracle", action="store_true",
                    help="long-trace oracle-regret benchmark (adaptive "
                         "steady state vs exhaustive per-workload "
                         "oracle); writes BENCH_oracle.json")
    ap.add_argument("--tenants", type=int, default=3,
                    help="isolated tenants for --serve-oracle")
    ap.add_argument("--oracle-rounds", type=int, default=12,
                    help="trace rounds over the program mix for "
                         "--serve-oracle")
    ap.add_argument("--oracle-scale", type=int, default=8,
                    help="dataset scale index for --serve-oracle")
    ap.add_argument("--model-eval", action="store_true",
                    help="leave-one-program-out model quality: learnt "
                         "MLP vs heuristic vs oracle on one profiled "
                         "corpus; writes BENCH_model.json")
    ap.add_argument("--eval-epochs", type=int, default=600,
                    help="MLP epochs per LOO fold for --model-eval")
    ap.add_argument("--eval-datasets", type=int, default=2,
                    help="dataset scales per program for --model-eval")
    args = ap.parse_args()
    enable_compile_cache()

    if args.model_eval:
        print("name,us_per_call,derived")
        for row in model_eval(
                args.programs.split(",") if args.programs else None,
                datasets=args.eval_datasets, reps=args.reps,
                epochs=args.eval_epochs,
                json_path=args.serve_json or "BENCH_model.json"):
            print(row)
        return

    if args.serve_real_trace:
        print("name,us_per_call,derived")
        for row in serve_real_trace(
                n_requests=args.real_trace_requests,
                seed=args.trace_seed, window=args.serve_window,
                workers=args.serve_workers,
                scale_index=args.real_trace_scale,
                backend=args.serve_backend,
                profile_alloc=args.profile_alloc,
                chrome_trace=args.chrome_trace,
                metrics_out=args.metrics_out,
                json_path=args.serve_json or "BENCH_overhead.json"):
            print(row)
        return

    if args.serve_chaos:
        print("name,us_per_call,derived")
        for row in serve_chaos(
                n_requests=args.chaos_requests, seed=args.trace_seed,
                window=args.serve_window, workers=args.serve_workers,
                backend=args.chaos_backend,
                fault_schedule=args.fault_schedule,
                watchdog_s=args.chaos_watchdog_ms / 1e3,
                json_path=args.serve_json or "BENCH_resilience.json"):
            print(row)
        return

    if args.serve_fleet:
        print("name,us_per_call,derived")
        for row in serve_fleet(
                args.programs.split(",") if args.programs else None,
                n_workers=args.fleet_workers,
                n_requests=args.fleet_requests,
                window=args.fleet_window,
                backend=args.serve_backend,
                scale_index=args.fleet_scale,
                tenants=args.fleet_tenants,
                reps=args.fleet_reps,
                kill_drill=not args.no_kill_drill,
                json_path=args.serve_json or "BENCH_fleet.json"):
            print(row)
        return

    if args.serve_trace:
        print("name,us_per_call,derived")
        for row in serve_latency_trace(
                n_requests=args.trace_requests, seed=args.trace_seed,
                window=args.serve_window,
                json_path=args.serve_json or "BENCH_latency.json"):
            print(row)
        return

    if args.serve_oracle:
        print("name,us_per_call,derived")
        for row in serve_oracle_trace(
                args.programs.split(",") if args.programs else None,
                tenants=args.tenants, rounds=args.oracle_rounds,
                backend=args.serve_backend,
                window=args.serve_window, workers=args.serve_workers,
                scale_index=args.oracle_scale,
                json_path=args.serve_json or "BENCH_oracle.json"):
            print(row)
        return

    if args.serve_concurrent:
        print("name,us_per_call,derived")
        for row in serve_concurrent_trace(
                args.programs.split(",") if args.programs else None,
                n_requests=args.serve_requests,
                backend=args.serve_backend,
                window=args.serve_window, workers=args.serve_workers,
                scale_index=args.serve_scale,
                json_path=args.serve_json or "BENCH_serving.json"):
            print(row)
        return

    if args.compare_backends:
        print("name,us_per_call,derived")
        for row in compare_backends(
                args.programs.split(",") if args.programs else None,
                reps=max(args.reps, 3)):
            print(row)
        return

    if args.serve:
        print("name,us_per_call,derived")
        for row in serve_trace(
                args.programs.split(",") if args.programs else None,
                n_requests=args.serve_requests,
                backend=args.serve_backend,
                json_path=args.serve_json):
            print(row)
        return

    if args.programs:
        programs = args.programs.split(",")
    elif args.quick:
        programs = QUICK_PROGRAMS
    else:
        programs = None  # all 39

    samples = ds.generate(programs, datasets_per_program=args.datasets,
                          reps=args.reps, verbose=True)
    print(f"# {len(samples)} profiled samples over "
          f"{len({s.program for s in samples})} programs")
    print("name,us_per_call,derived")

    for row in pf.fig2_heatmap(samples):
        print(row)
    fig9_rows, summary = pf.fig9_overall(samples)
    for row in fig9_rows:
        print(row)
    for row in pf.fig10_fixed(samples):
        print(row)
    for row in pf.fig12_analytical(samples):
        print(row)
    for row in pf.fig14_classifier(samples):
        print(row)
    for row in pf.table5_models(samples):
        print(row)
    for row in pf.search_overhead(samples):
        print(row)
    for row in dryrun_summary():
        print(row)
    print(f"# SUMMARY ours={summary['ours']:.3f}x "
          f"oracle={summary['oracle']:.3f}x "
          f"pct_of_oracle={summary['pct']:.1f}%")


if __name__ == "__main__":
    main()
