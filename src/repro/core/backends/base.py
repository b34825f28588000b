"""Executor-backend protocol: how a (partitions, tasks) stream config is
realized on a concrete substrate.

A backend receives an :class:`ExecutionContext` — the immutable per-run
state (kernel, host data, device, jitted callables, resident shared
buffers) — and a :class:`~repro.core.stream_config.StreamConfig`, and
returns the list of per-slice outputs in deterministic (task-major,
partition-minor) order.  That ordering contract is what makes every
backend comparable against the single-stream reference: concatenating the
outputs along axis 0 must reproduce the unsplit result for ``concat``
workloads.

Two backend kinds exist:
  * ``runner``     — drives a chunkable data-parallel kernel
                     (``dispatch`` is the entry point);
  * ``train-step`` — rewrites a training step into a streamed equivalent
                     (``wrap_train_step`` is the entry point).
"""
from __future__ import annotations

import abc
import dataclasses
import threading
from typing import Any, Callable, Optional

import jax
import numpy as np


class _NoSpan:
    """The shared no-op context manager :func:`no_span` hands out."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


NO_SPAN = _NoSpan()


def no_span(name: str) -> _NoSpan:
    """The default span factory of :meth:`StreamBackend.dispatch`: every
    phase gets the one shared no-op, so an untraced dispatch reads no
    clock and allocates nothing per task.  A tracing caller passes its
    own factory instead (the serving scheduler passes ``tracer.span``),
    called with the phase name alone."""
    return NO_SPAN


# Process-wide jit memo: serving creates one ExecutionContext per request,
# and a fresh ``jax.jit(kernel)`` wrapper per request would recompile every
# shape it has already seen.  Workload kernels are module-level callables
# with stable identity, so memoizing the wrapper by kernel shares the trace
# cache across contexts (and across requests for the whole process).
# Bounded with FIFO eviction: a jitted wrapper strongly references its
# kernel, so a weak-keyed map would never collect entries anyway, and
# callers jitting dynamically created closures must not grow the memo (and
# every compiled executable behind it) without bound.
_JIT_MEMO: dict = {}
_JIT_MEMO_MAX = 256
# miss-path lock: backends run on pool worker threads (host-threads, the
# concurrent serving engine), and an unguarded evict-while-full loop lets
# two threads pop the same key
_MEMO_LOCK = threading.Lock()


def memoized_jit(kernel: Callable, *, donate: bool = False) -> Callable:
    """``jax.jit(kernel)`` with the wrapper shared across ExecutionContexts."""
    try:
        entry = _JIT_MEMO.get(kernel)
    except TypeError:          # unhashable callable: no memoization
        return (jax.jit(kernel, donate_argnums=0) if donate
                else jax.jit(kernel))
    key = "donate" if donate else "plain"
    if entry is not None and key in entry:
        return entry[key]
    with _MEMO_LOCK:
        entry = _JIT_MEMO.get(kernel)
        if entry is None:
            while len(_JIT_MEMO) >= _JIT_MEMO_MAX:
                _JIT_MEMO.pop(next(iter(_JIT_MEMO)), None)
            entry = _JIT_MEMO[kernel] = {}
        if key not in entry:
            entry[key] = (jax.jit(kernel, donate_argnums=0) if donate
                          else jax.jit(kernel))
        return entry[key]


def split_arrays(arrs: dict, n: int) -> list[dict]:
    """Split every array in the dict into n chunks along axis 0."""
    if n == 1:
        return [arrs]
    keys = list(arrs)
    pieces = {k: np.array_split(arrs[k], n) for k in keys}
    return [{k: pieces[k][i] for k in keys} for i in range(n)]


# Dispatch-plan cache: the (start, stop) row ranges of every task and
# partition slice depend only on (row count, config), yet the backends used
# to re-derive them through nested ``np.array_split`` calls on every
# dispatch.  Serving traffic repeats the same few (shape-bucket, config)
# pairs thousands of times, so the boundaries are memoized here and the
# arrays sliced directly — the hot-path cost per dispatch drops to plain
# ``a[lo:hi]`` views.
_PLAN_CACHE: dict = {}
_PLAN_CACHE_MAX = 4096


def _split_bounds(lo: int, hi: int, n: int) -> list[tuple[int, int]]:
    """(start, stop) ranges identical to ``np.array_split`` of hi-lo rows
    into n pieces (first ``rem`` pieces get the extra row)."""
    total = hi - lo
    base, rem = divmod(total, n)
    bounds = []
    start = lo
    for i in range(n):
        size = base + (1 if i < rem else 0)
        bounds.append((start, start + size))
        start += size
    return bounds


def dispatch_plan(n_rows: int, config) -> tuple:
    """Memoized slicing plan for one dispatch: a tuple of tasks, each a
    tuple of global (start, stop) partition row ranges — task-major,
    partition-minor, byte-identical boundaries to the nested
    ``split_arrays`` the backends used to compute per call.

    Thread-safe: backends dispatch from pool workers, so the eviction
    loop runs under the shared memo lock (the hit path stays lock-free —
    a racy ``get`` of an immutable tuple is fine)."""
    key = (n_rows, config.partitions, config.tasks)
    plan = _PLAN_CACHE.get(key)
    if plan is None:
        with _MEMO_LOCK:
            plan = _PLAN_CACHE.get(key)
            if plan is None:
                while len(_PLAN_CACHE) >= _PLAN_CACHE_MAX:
                    _PLAN_CACHE.pop(next(iter(_PLAN_CACHE)), None)
                plan = tuple(
                    tuple(_split_bounds(t_lo, t_hi, config.partitions))
                    for t_lo, t_hi in _split_bounds(0, n_rows, config.tasks))
                _PLAN_CACHE[key] = plan
    return plan


def slice_rows(arrs: dict, lo: int, hi: int) -> dict:
    """Row-range view of every array in the dict (no copies)."""
    return {k: a[lo:hi] for k, a in arrs.items()}


@dataclasses.dataclass
class ExecutionContext:
    """Per-(workload, dataset) state shared by every runner backend."""

    kernel: Callable
    chunked: dict
    shared: dict
    device: Any
    jit_kernel: Callable
    shared_dev: Any
    _donating_jit: Optional[Callable] = None

    @classmethod
    def create(cls, kernel: Callable, chunked: dict, shared: dict,
               device=None) -> "ExecutionContext":
        device = device or jax.devices()[0]
        # buffer-validity tracking (paper §4.4.5): shared buffers are
        # transferred once and stay resident across tasks and runs.
        shared_dev = jax.device_put(shared, device)
        jax.block_until_ready(shared_dev)
        return cls(kernel=kernel, chunked=chunked, shared=shared,
                   device=device, jit_kernel=memoized_jit(kernel),
                   shared_dev=shared_dev)

    def swap_buffers(self, chunked: dict, shared: dict) -> "ExecutionContext":
        """Re-point this context at a new request's data, keeping the
        jitted handles and device.

        The shared-buffer H2D transfer is semantically required when the
        new request carries shared data (its values differ), but a
        workload with an empty shared dict pays nothing — which is what
        makes pooling contexts cheaper than rebuilding them: creation
        always round-trips through ``device_put`` + ``block_until_ready``,
        a swap only does when there is something to ship."""
        self.chunked = chunked
        self.shared = shared
        if shared:
            self.shared_dev = jax.device_put(shared, self.device)
            jax.block_until_ready(self.shared_dev)
        else:
            self.shared_dev = {}
        return self

    @property
    def donating_jit(self) -> Callable:
        """Kernel jitted with the chunk argument donated, so a finished
        task's device buffers are recycled for its outputs (no-op on
        backends without donation support, e.g. CPU)."""
        if self._donating_jit is None:
            self._donating_jit = memoized_jit(self.kernel, donate=True)
        return self._donating_jit


class StreamBackend(abc.ABC):
    """One realization of the streamed-execution strategy."""

    #: unique registry key
    name: str = ""
    #: "runner" (chunkable kernels) or "train-step" (training loops)
    kind: str = "runner"

    def dispatch(self, ctx: ExecutionContext, config, *,
                 span: Callable = no_span) -> list:
        """Issue the full iteration space under ``config``; returns the
        per-slice outputs (possibly still in flight — callers block).
        A backend that blocks on the calling thread before it returns
        wraps each such wait in ``span("dispatch.wait")``."""
        raise NotImplementedError(f"{self.name} is not a runner backend")

    def wrap_train_step(self, loss_fn: Callable, config, *,
                        unroll: bool = True) -> Callable:
        """Rewrite ``loss_fn(params, batch) -> (loss, aux)`` into a
        streamed step function."""
        raise NotImplementedError(f"{self.name} is not a train-step backend")

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"<StreamBackend {self.name} ({self.kind})>"
