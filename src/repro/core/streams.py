"""The streamed executor — AUTOSTREAMER's runtime.

The execution strategies themselves live in :mod:`repro.core.backends`
(``host-sync``, ``host-pipelined``, ``mesh``, plus anything registered at
runtime).  This module keeps the user-facing runner: one object per
(workload, dataset) pair that can execute, time, and profile arbitrary
stream configs on any registered runner backend.

``streamify_train_step`` is the train-step face of the same idea and
delegates to the ``mesh`` backend.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Union

import jax
import numpy as np

from repro.core.backends import (StreamBackend, ExecutionContext,
                                 get_backend, no_span, split_arrays)
from repro.core.stream_config import SINGLE_STREAM, StreamConfig
from repro.core.workloads import Workload

# back-compat alias: tests and older callers import the splitter from here
_split = split_arrays


def readback_outputs(outs: list) -> None:
    """Materialize EVERY output leaf on the host (paper Fig 8c: results
    transferred back).  Reading only the first leaf — the old behavior —
    undercounts D2H time on multi-output kernels, so every measured
    runtime (``run``, the serving execute stage) routes through here."""
    for o in outs:
        for leaf in jax.tree.leaves(o):
            np.asarray(leaf, copy=False)


class StreamedRunner:
    """Executes one workload+dataset under arbitrary stream configs.

    ``backend`` picks the execution strategy by registry name (or a
    :class:`StreamBackend` instance); every runner backend produces
    outputs in the same task-major order, allclose to the single-stream
    reference.
    """

    def __init__(self, wl: Workload, chunked: dict, shared: dict,
                 device=None, backend: Union[str, StreamBackend] = "host-sync",
                 ctx: Union[ExecutionContext, None] = None):
        self.wl = wl
        self.chunked = chunked
        self.shared = shared
        self.backend = (get_backend(backend) if isinstance(backend, str)
                        else backend)
        if self.backend.kind != "runner":
            raise ValueError(
                f"backend {self.backend.name!r} is a {self.backend.kind} "
                f"backend, not a runner")
        # a caller holding a pooled ExecutionContext (the serving engine's
        # per-workload context pool) wraps it instead of paying create()'s
        # shared-buffer upload again
        self.ctx = ctx if ctx is not None else ExecutionContext.create(
            wl.kernel, chunked, shared, device)
        self.device = self.ctx.device
        # legacy attribute names, still used by feature extraction
        self._jit = self.ctx.jit_kernel
        self._shared_dev = self.ctx.shared_dev

    # -- execution -----------------------------------------------------------

    def dispatch(self, config: StreamConfig, *,
                 span: Callable = no_span) -> list:
        """Issue the full iteration space under ``config``; returns the
        per-slice outputs (possibly still in flight — callers block).
        ``span`` is the backend's phase-span factory; untraced, the
        backend is called without it, so a backend written to the
        two-argument form keeps working."""
        if span is no_span:
            return self.backend.dispatch(self.ctx, config)
        return self.backend.dispatch(self.ctx, config, span=span)

    # legacy private name, used by older tests
    _dispatch = dispatch

    def warmup(self, config: StreamConfig) -> None:
        """Compile every sub-slice shape before timing."""
        outs = self._dispatch(config)
        jax.block_until_ready(outs)

    def run(self, config: StreamConfig, *, reps: int = 3,
            warmed: bool = False) -> float:
        """Wall-clock seconds (min over reps) incl. H2D, compute, D2H."""
        if not warmed:
            self.warmup(config)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            outs = self._dispatch(config)
            # read back (paper Fig 8c: results transferred to host)
            jax.block_until_ready(outs)
            readback_outputs(outs)
            best = min(best, time.perf_counter() - t0)
        return best

    def run_single_stream(self, *, reps: int = 3) -> float:
        return self.run(SINGLE_STREAM, reps=reps)

    # -- profiling hooks used by feature extraction ---------------------------

    def measure_transfer(self, *, reps: int = 3) -> float:
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            dev = jax.device_put(self.chunked, self.device)
            jax.block_until_ready(dev)
            best = min(best, time.perf_counter() - t0)
        return best

    def measure_compute(self, *, reps: int = 3) -> float:
        dev = jax.device_put(self.chunked, self.device)
        jax.block_until_ready(dev)
        self.warmup(SINGLE_STREAM)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = self._jit(dev, self._shared_dev)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        return best

    def lowered_kernel(self):
        """Lowered+compiled single-chunk kernel for static features."""
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.chunked)
        sshapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.shared)
        return jax.jit(self.wl.kernel).lower(shapes, sshapes)


def parallel_capacity(calls, workers: int, *, reps: int = 8,
                      trials: int = 2) -> float:
    """Calibrate the host: how much does issuing ``calls`` from
    ``workers`` threads speed up over serial issue?

    ``calls`` are zero-arg callables that block until their work is
    done (compiled, device-resident kernels — so the ratio is the raw
    hardware scaling ceiling, not compile or H2D noise).  Max over
    ``trials`` serial/threaded pairs, because steal time on shared
    boxes deflates single trials.  This one number is consumed twice:
    the ``--serve-concurrent`` benchmark reports it as the ceiling the
    engine chases, and the concurrent engine's load-aware drift signal
    divides in-flight occupancy by it to normalize contention out of
    ``measured_s`` before drift detection."""
    import concurrent.futures

    n = max(1, reps) * len(calls)

    def one(i: int) -> None:
        calls[i % len(calls)]()

    pool = concurrent.futures.ThreadPoolExecutor(workers)
    try:
        best = 0.0
        for _ in range(max(1, trials)):
            t0 = time.perf_counter()
            for i in range(n):
                one(i)
            t_serial = time.perf_counter() - t0
            t0 = time.perf_counter()
            futs = [pool.submit(one, i) for i in range(n)]
            for f in futs:
                f.result()
            t_threaded = time.perf_counter() - t0
            best = max(best, t_serial / max(t_threaded, 1e-12))
    finally:
        pool.shutdown()
    return best


def probe_host_capacity(workers: int, *, size: int = 384,
                        reps: int = 6) -> float:
    """Capacity probe with a synthetic kernel (one compiled matmul) for
    callers that have no workload in hand yet — the concurrent engine's
    lazy calibration path.  Costs a few milliseconds once."""
    x = np.random.default_rng(0).standard_normal(
        (size, size)).astype(np.float32)
    jitk = jax.jit(lambda a: a @ a)
    dev = jax.device_put(x)
    jax.block_until_ready(jitk(dev))            # compile, untimed
    return parallel_capacity(
        [lambda: jax.block_until_ready(jitk(dev))], workers, reps=reps)


def slice_probe(chunk: dict, shared: dict):
    """The slice-overhead probe's kernel: vecadd's shape of work, two
    chunked inputs and one output."""
    return chunk["a"] + chunk["b"]


#: the probe's payload (16 Ki float32 rows an input, 64 KiB) and split:
#: at 1 KiB-row slices the bytes are negligible next to what a slice
#: costs the host
PROBE_ROWS = 16 * 1024
PROBE_TASKS = 16
_PROBE_WORKLOAD = Workload("slice_probe", "probe", slice_probe,
                           make_data=None, datasets=())
# (backend name, device) -> seconds; its own lock, held across the
# probe so that engines sharing a process share one probe and one
# compile (the probe's dispatches take the backends' memo lock)
_SLICE_OVERHEAD: dict = {}
_PROBE_LOCK = threading.Lock()


def probe_slice_overhead(backend: Union[str, StreamBackend],
                         device=None) -> float:
    """Seconds each extra slice of a split costs on ``device`` under
    ``backend``: a ``device_put`` per input, a kernel launch, a window
    retire and a read-back per output.  A small payload runs at 1x1 and
    at 1x``PROBE_TASKS``, interleaved (``profile_grid_interleaved``, min
    over 5 sweeps, on ``run``'s basis: dispatch to read-back); the
    overhead is their difference over the extra slices, never negative.
    Memoized per process by (backend name, device)."""
    backend = get_backend(backend) if isinstance(backend, str) else backend
    device = device or jax.devices()[0]
    key = (backend.name, device)
    overhead_s = _SLICE_OVERHEAD.get(key)
    if overhead_s is not None:
        return overhead_s
    with _PROBE_LOCK:
        overhead_s = _SLICE_OVERHEAD.get(key)
        if overhead_s is None:
            rng = np.random.default_rng(0)
            chunked = {k: rng.standard_normal(PROBE_ROWS, dtype=np.float32)
                       for k in ("a", "b")}
            runner = StreamedRunner(_PROBE_WORKLOAD, chunked, {},
                                    device=device, backend=backend)
            split = StreamConfig(1, PROBE_TASKS)
            t = profile_grid_interleaved(runner, [SINGLE_STREAM, split],
                                         sweeps=5)
            overhead_s = _SLICE_OVERHEAD[key] = max(
                0.0, (t[split] - t[SINGLE_STREAM]) / (PROBE_TASKS - 1))
    return overhead_s


def profile_config_grid(runner: StreamedRunner, configs, *, reps: int = 3,
                        verbose: bool = False) -> dict[StreamConfig, float]:
    """Exhaustive profiling of a config grid (paper §3.1.2)."""
    out = {}
    for cfg in configs:
        out[cfg] = runner.run(cfg, reps=reps)
        if verbose:
            print(f"  {cfg.partitions:3d}x{cfg.tasks:<3d} {out[cfg]*1e3:8.3f} ms")
    return out


def profile_grid_interleaved(runner: StreamedRunner, configs, *,
                             sweeps: int = 3,
                             prior: Union[dict, None] = None
                             ) -> dict[StreamConfig, float]:
    """Min-per-config over round-robin sweeps of the grid.

    Interleaving beats back-to-back reps on shared boxes: a
    neighbor-load spike spans one sweep's worth of configs, not every
    sample of one config, so the per-config min survives it and the
    argmin is not a lottery.  ``prior`` merges a previous profile of the
    same configs (the oracle benchmark's before/after-serving passes).
    This is THE measurement protocol for config selection — the serving
    refiner and the oracle-regret benchmark both use it, so the
    "achieved" and "oracle" sides of the regret ratio are measured
    identically."""
    best = dict(prior) if prior else {c: float("inf") for c in configs}
    for c in configs:
        runner.warmup(c)
    for _ in range(max(1, sweeps)):
        for c in configs:
            best[c] = min(best[c], runner.run(c, reps=1, warmed=True))
    return best


def streamify_train_step(
    loss_fn: Callable,
    config: StreamConfig,
    *,
    unroll: bool = True,
) -> Callable:
    """Microbatched grad-accumulation step — see
    :meth:`repro.core.backends.mesh.MeshBackend.wrap_train_step`."""
    return get_backend("mesh").wrap_train_step(loss_fn, config,
                                               unroll=unroll)
