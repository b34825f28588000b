"""Thread-pool host backend: tasks issued from worker threads with a
bounded in-flight window.

Where ``host-pipelined`` overlaps H2D and compute by interleaving async
dispatches from one host thread, this backend overlaps them by issuing
each task (transfer + kernel dispatch + retire) from a pool thread — the
host-side analogue of multiple hardware queues.  JAX dispatch is
thread-safe; concurrent tracing of the same shape serializes on JAX's own
compilation lock, so the first dispatch per shape costs the same as the
single-threaded backends.

Ordering contract: outputs are collected into a task-indexed slot table,
so the returned list is task-major, partition-minor regardless of the
completion order of the workers.

The pool machinery lives in :class:`WindowedPool` so other consumers —
the concurrent serving engine (:mod:`repro.serving.engine`) overlaps
whole *requests* on the same primitive — get the lazy executor and the
bounded-window discipline without reimplementing it.
"""
from __future__ import annotations

import collections
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Optional, Sequence

import jax

from repro.core.backends.base import ExecutionContext, StreamBackend, \
    dispatch_plan, no_span, slice_rows


class WindowedPool:
    """A lazily created thread pool plus a bounded in-flight window.

    ``window`` bounds how many submitted items may be un-retired at once
    — the live-buffer bound the pipelined backend gets from its
    depth-``d`` deque, enforced here by blocking the submitting thread on
    the oldest outstanding future.
    """

    def __init__(self, workers: int = 4, window: int = 8,
                 name: str = "windowed-pool"):
        assert workers >= 1 and window >= 1, (workers, window)
        self.workers = workers
        self.window = window
        self.name = name
        self._pool: Optional[ThreadPoolExecutor] = None

    def executor(self) -> ThreadPoolExecutor:
        # lazy: module import registers backend instances, and spawning
        # threads at import time would cost every process that never
        # dispatches
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self.workers, thread_name_prefix=self.name)
        return self._pool

    def submit(self, fn: Callable, *args) -> Future:
        return self.executor().submit(fn, *args)

    def run_ordered(self, fn: Callable, items: Sequence) -> list:
        """``[fn(x) for x in items]`` on the pool: submission order, at
        most ``window`` in flight, results in item order regardless of
        completion order."""
        pool = self.executor()
        results: list = [None] * len(items)
        inflight: collections.deque = collections.deque()
        for i, item in enumerate(items):
            while len(inflight) >= self.window:
                j, fut = inflight.popleft()
                results[j] = fut.result()
            inflight.append((i, pool.submit(fn, item)))
        while inflight:
            j, fut = inflight.popleft()
            results[j] = fut.result()
        return results

    def shutdown(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None


class ThreadedHostBackend(StreamBackend):
    name = "host-threads"
    kind = "runner"

    def __init__(self, workers: int = 4, window: int = 8):
        self.pool = WindowedPool(workers, window, name="host-threads")
        self.workers = workers
        self.window = window

    def dispatch(self, ctx: ExecutionContext, config, *,
                 span=no_span) -> list:
        # the waits happen on the pool's threads, outside the caller's
        # spans: ``span`` has no phase to wrap
        n_rows = next(iter(ctx.chunked.values())).shape[0]
        plans = dispatch_plan(n_rows, config)

        def issue(parts):
            devs = [jax.device_put(slice_rows(ctx.chunked, lo, hi),
                                   ctx.device) for lo, hi in parts]
            outs = [ctx.jit_kernel(pd, ctx.shared_dev) for pd in devs]
            # retire inside the worker: a completed future means the
            # task's buffers are no longer accumulating in flight
            jax.block_until_ready(outs)
            return outs

        results = self.pool.run_ordered(issue, plans)
        return [o for task_outs in results for o in task_outs]
