"""Pluggable executor backends for the streamed runtime.

A *backend* is one realization of the paper's (partitions, tasks)
execution strategy on a concrete substrate.  Backends register under a
string name; the runner (:class:`repro.core.streams.StreamedRunner`), the
autotuner, and the tuning cache all address them by that name, so a
serving process can switch substrates — or A/B two host pipelines — with
a config string.

Built-ins:
  ``host-sync``      — the synchronous reference executor (seed behavior)
  ``host-pipelined`` — depth-2 double-buffered pipeline with host-side
                       partition slicing and buffer donation
  ``host-threads``   — thread-pool task issue with a bounded in-flight
                       window (host-side analogue of multiple HW queues)
  ``mesh``           — pod-scale microbatched training step

Adding a backend::

    from repro.core.backends import StreamBackend, register_backend

    class MyBackend(StreamBackend):
        name = "my-backend"
        def dispatch(self, ctx, config, *, span=no_span): ...

    register_backend(MyBackend())
"""
from __future__ import annotations

from repro.core.backends.base import (ExecutionContext, StreamBackend,
                                      dispatch_plan, memoized_jit, no_span,
                                      slice_rows, split_arrays)
from repro.core.backends.host_pipelined import PipelinedHostBackend
from repro.core.backends.host_sync import SyncHostBackend
from repro.core.backends.host_threads import ThreadedHostBackend, \
    WindowedPool
from repro.core.backends.mesh import MeshBackend

_BACKENDS: dict[str, StreamBackend] = {}

#: the numerical reference every runner backend must reproduce
REFERENCE_BACKEND = "host-sync"


def register_backend(backend: StreamBackend, *,
                     overwrite: bool = False) -> StreamBackend:
    """Register a backend instance under ``backend.name``."""
    if not backend.name:
        raise ValueError(f"{backend!r} has no name")
    if backend.name in _BACKENDS and not overwrite:
        raise ValueError(f"backend {backend.name!r} already registered "
                         f"(pass overwrite=True to replace)")
    _BACKENDS[backend.name] = backend
    return backend


def get_backend(name: str) -> StreamBackend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise KeyError(
            f"unknown backend {name!r}; registered: {list_backends()}"
        ) from None


def list_backends(kind: str | None = None) -> list[str]:
    """Sorted names of registered backends, optionally filtered by kind
    (``"runner"`` or ``"train-step"``)."""
    return sorted(n for n, b in _BACKENDS.items()
                  if kind is None or b.kind == kind)


register_backend(SyncHostBackend())
register_backend(PipelinedHostBackend())
register_backend(ThreadedHostBackend())
register_backend(MeshBackend())

__all__ = [
    "ExecutionContext", "StreamBackend", "memoized_jit", "split_arrays",
    "dispatch_plan", "slice_rows", "no_span", "WindowedPool",
    "SyncHostBackend", "PipelinedHostBackend", "ThreadedHostBackend",
    "MeshBackend",
    "register_backend", "get_backend", "list_backends",
    "REFERENCE_BACKEND",
]
