"""The synchronous host backend — the seed executor, unchanged semantics.

Mirrors Figure 8c of the paper on a single host device:
  * the outer iteration space is split into ``tasks`` chunks;
  * each chunk's host->device transfer (``jax.device_put``) is issued
    asynchronously and overlaps the (async-dispatched) compute of earlier
    chunks — temporal sharing;
  * each chunk's kernel is dispatched as ``partitions`` sub-slices, which
    sets the kernel working-set granularity (cache blocking) and dispatch
    parallelism — the spatial-sharing analogue on a host backend.

The host loop runs ahead without bound: nothing caps how many tasks are
in flight, and each task's buffers are fresh allocations.  The pipelined
sibling (:mod:`repro.core.backends.host_pipelined`) fixes both.
"""
from __future__ import annotations

import jax

from repro.core.backends.base import ExecutionContext, StreamBackend, \
    dispatch_plan, no_span, slice_rows


class SyncHostBackend(StreamBackend):
    name = "host-sync"
    kind = "runner"

    def dispatch(self, ctx: ExecutionContext, config, *,
                 span=no_span) -> list:
        # nothing here blocks: ``span`` has no phase to wrap
        n_rows = next(iter(ctx.chunked.values())).shape[0]
        outs = []
        for parts in dispatch_plan(n_rows, config):
            t_lo = parts[0][0]
            task = slice_rows(ctx.chunked, t_lo, parts[-1][1])
            task_dev = jax.device_put(task, ctx.device)     # async H2D
            # partition slicing still happens on the DEVICE chunk — the
            # deliberate seed flaw the pipelined sibling fixes
            for p_lo, p_hi in parts:
                part = slice_rows(task_dev, p_lo - t_lo, p_hi - t_lo)
                outs.append(ctx.jit_kernel(part, ctx.shared_dev))
        return outs
