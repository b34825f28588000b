"""The default engine: one ``ConcurrentScheduler`` on the overlap
heuristic, in the harness's own process, serving on its first device.

Set-up probes the engine's parallel capacity as its first ``run()``
would, fills the tuning cache as the traffic mix asks (``filled``:
one request of each bucket through this engine; ``empty``: a throwaway
engine tunes them, so that every shape a cold tune compiles is in the
cache) and warms every chunk shape a candidate split can give.  The
profiler, the compile listener and the memory peak are this process's.
"""
from __future__ import annotations

import math
import os
import time
from pathlib import Path

import numpy as np

import devtrace
from engine_api import Chip, Collected, Served, Trace, spans_of
from harness import BenchError

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the chip is this process's: the harness's
OWN_CHIPS = True


def open(config: dict, traffic: dict, *, trace: bool, work: Path):
    return Concurrent(config, traffic, trace=trace, work=work)


class Concurrent:
    def __init__(self, config: dict, traffic: dict, *, trace: bool,
                 work: Path):
        import jax

        self.config, self.traffic = config, traffic
        self.work = work
        self.compile_times: list = []
        self.anchor = None
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self.tracer = None
        if trace:
            from repro.serving.observability import Tracer
            self.tracer = Tracer()
        self.engine = None

    def chips(self) -> list:
        import jax

        self.device = jax.devices()[0]
        return [Chip(platform=self.device.platform,
                     kind=self.device.device_kind)]

    # -- set-up ---------------------------------------------------------------

    def setup(self, buckets: dict, tenants: list) -> dict:
        from repro.core.stream_config import SINGLE_STREAM
        from repro.core.streams import StreamedRunner
        from repro.core.workloads import get_workload

        import datagen

        tr = self.traffic
        self.engine = self._engine(self.tracer)
        self._stamp_pops(self.engine)
        _ = self.engine.parallel_capacity     # the probe run() would make
        picks: dict = {}
        if tr["tuning_cache"] == "filled":
            who = tenants if tr["isolate_tenants"] else tenants[:1]
            picks = self._serve_once(self.engine, [(t, b) for t in who
                                                   for b in buckets], buckets)
        elif tr["tuning_cache"] == "empty":
            # a throwaway engine cold-tunes every bucket, each under a
            # tenant of its own: buckets whose row counts round to one
            # cache bucket would otherwise share one feature extraction,
            # and the others' shapes would compile in the window
            scratch = self._engine(None, isolate=True)
            self._serve_once(scratch, [(f"warm-{i}", b)
                                       for i, b in enumerate(buckets)],
                             buckets)
            scratch.close()
        else:
            raise BenchError(f"tuning_cache {tr['tuning_cache']!r}")
        # every chunk shape a candidate split can give, so that a cold
        # tune or a drift refinement in the window compiles nothing: a
        # split's kernel calls take floor or ceil of rows / (partitions *
        # tasks) rows, and each such shape is warmed by one single-stream
        # dispatch of that many rows
        backend = self.config["engine"]["backend"]
        qs = {c.partitions * c.tasks for c in self.engine.candidates}
        for (prog, rows), (ch, sh) in buckets.items():
            sizes = {f(rows / q) for q in qs if q <= rows
                     for f in (math.floor, math.ceil)}
            for m in sorted(sizes):
                StreamedRunner(get_workload(prog),
                               datagen.request_view(ch, 0, m), sh,
                               backend=backend).warmup(SINGLE_STREAM)
        return picks

    def _engine(self, tracer, isolate: bool | None = None):
        from repro.core.modeling.heuristic import OverlapHeuristicModel
        from repro.serving.engine import ConcurrentScheduler
        from repro.serving.refinement import DriftDetector

        eng = self.config["engine"]
        if eng["model"] != "heuristic":
            raise BenchError(f"model {eng['model']!r} is not supported")
        # "drift": "off" is a detector that never fires: no refinement
        # re-profiles in the window; the engine's own detector otherwise
        drift = {"on": None, "off": DriftDetector(threshold=math.inf)}[
            eng.get("drift", "on")]
        return ConcurrentScheduler(
            OverlapHeuristicModel(), window=int(eng["window"]),
            workers=int(eng["workers"]), backend=eng["backend"],
            isolate_tenants=bool(self.traffic["isolate_tenants"]
                                 if isolate is None else isolate),
            drift=drift, tracer=tracer)

    def _stamp_pops(self, engine) -> None:
        """Stamp each request when the engine takes it off its queue (the
        engine's clock is the same ``perf_counter``)."""
        self.popped: dict = {}
        pop = engine.queue.pop

        def stamped():
            req = pop()
            self.popped[req.seq] = time.perf_counter()
            return req

        engine.queue.pop = stamped

    def _serve_once(self, engine, who: list, buckets: dict) -> dict:
        """Serve one request of each ``(tenant, bucket)`` in ``who``;
        returns the split each bucket was served under."""
        from repro.serving.queue import WorkloadRequest

        reqs = {}
        for t, bucket in who:
            ch, sh = buckets[bucket]
            req = WorkloadRequest(bucket[0], ch, sh, tenant=t)
            reqs[id(req)] = bucket
            engine.submit(req)
        return {reqs[id(r.request)]: (r.config.partitions, r.config.tasks)
                for r in engine.run()}

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.compile_times.append(time.perf_counter())

    # -- the window -----------------------------------------------------------

    def begin(self) -> None:
        if self.tracer is not None:
            self.tracer.clear()

    def serve(self, requests: list) -> list:
        from repro.serving.queue import WorkloadRequest

        sent = {}
        for q in requests:
            req = WorkloadRequest(q.program, q.chunked, q.shared,
                                  tenant=q.tenant)
            sent[id(req)] = q
            self.engine.submit(req)
        out = []
        for r in self.engine.run():
            q = sent[id(r.request)]
            out.append(Served(
                key=q.key, ok=r.status in ("served", "degraded"),
                split=((r.config.partitions, r.config.tasks)
                       if r.config is not None else (0, 0)),
                t_pop=self.popped.pop(r.request.seq, math.nan),
                t_retire=r.sample.t_retire_s,
                in_bytes=_nbytes(q.chunked) + _nbytes(q.shared),
                out_bytes=sum(int(o.nbytes) for o in r.outputs),
                outputs=([np.asarray(o) for o in r.outputs] if q.check
                         else None)))
        return out

    def start_profile(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.work), profiler_options=opts)
        with jax.profiler.TraceAnnotation(devtrace.ANCHOR):
            self.anchor = time.perf_counter()

    def stop_profile(self) -> None:
        import jax

        jax.profiler.stop_trace()

    # -- after the window -----------------------------------------------------

    def collect(self) -> Collected:
        stats = self.device.memory_stats() or {}
        traces = []
        if self.anchor is not None:
            path = devtrace.find_xplane(str(self.work))
            if path:
                traces.append(Trace(events=devtrace.load(path),
                                    anchor=self.anchor, proc=os.getpid()))
        return Collected(
            spans=spans_of(self.tracer.spans) if self.tracer else [],
            compile_times=list(self.compile_times),
            memory_peaks=[int(stats.get("peak_bytes_in_use", 0))],
            traces=traces)

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)
        if self.engine is not None:
            self.engine.close()
            self.engine = None


def _nbytes(d: dict) -> int:
    return sum(int(a.nbytes) for a in d.values())
