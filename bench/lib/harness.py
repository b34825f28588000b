"""One run of one benchmark cell: set-up, the measured window, the check
against the plain reference, and the metrics.

Everything that belongs to one configuration, traffic mix, driver or
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

  <bench>/configs/<config>.json     programs, sizes, engine, limits
  <bench>/traffic/<mix>.json        the driver's name and parameters
  <bench>/drivers/<driver>.py       ``drive(window, traffic)``
  <bench>/metrics/<metric>.py       ``read(run) -> float | None``, and
                                    optionally ``setup(run)`` for the
                                    traced run's set-up
  <bench>/counts/<program>.py       ``counts(rows) -> (flops, bytes)``
  <bench>/reference/<program>.py    ``kernel(P, chunked, shared)``

From the program the harness takes only the serving engine it drives
(``ConcurrentScheduler`` on the heuristic model), the runner it warms
shapes with, and the engine's spans.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

import check
import datagen
import devtrace
import trafficgen

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: host threads the reference comparison runs on
CHECK_THREADS = 4
#: the profiled sub-window of a traced run: the first driver call that
#: starts this long into the window, to the first that ends this long
#: after the profiler started
TRACE_AFTER_S = 2.0
TRACE_FOR_S = 3.0


class BenchError(RuntimeError):
    """A run that cannot give a result: it exits non-zero and prints none."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_plugin(bench_dir: Path, kind: str, name: str, *,
                required: bool = True):
    """``<bench>/<kind>/<name>.py`` as a module, or None when absent and
    not required."""
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        if required:
            raise BenchError(f"no {kind} file {path}")
        return None
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    bench_dir: Path


def resolve(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, its files loaded."""
    bm = load_json(root / "BENCHMARK.json")
    bench_dir = root / bm["paths"][0]
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")

    def here(m: dict) -> bool:
        return "workloads" not in m or name in m["workloads"]

    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bm["end_to_end"] if here(m)],
                per_layer=[m for m in bm["per_layer"] if here(m)],
                bench_dir=bench_dir)


@dataclasses.dataclass
class Done:
    """One request of the window, as the readers see it."""

    program: str
    rows: int
    tenant: str
    split: tuple          # (partitions, tasks) it ran under
    t_pop: float          # the engine took it off its queue (host clock)
    t_retire: float       # the engine retired it (same clock)
    in_bytes: int
    out_bytes: int
    ok: bool
    traced: bool          # it ran inside the profiled sub-window


@dataclasses.dataclass
class Checked:
    item: trafficgen.Item
    split: tuple
    outputs: list         # host arrays, one per kernel call


@dataclasses.dataclass
class Run:
    """What a metric reader gets."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    setup_s: float = 0.0
    t_start: float = 0.0
    t_end: float = 0.0
    done: list = dataclasses.field(default_factory=list)
    spans: list = dataclasses.field(default_factory=list)
    compile_times: list = dataclasses.field(default_factory=list)
    device: dict | None = None        # devtrace.reduce of the sub-window
    peaks: dict | None = None
    picks: dict = dataclasses.field(default_factory=dict)
    store: dict = dataclasses.field(default_factory=dict)
    # set-up only: each bucket's (chunked, shared) host data
    buckets: dict = dataclasses.field(default_factory=dict)

    def counts(self, program: str):
        """The program's count module, or None."""
        return load_plugin(self.cell.bench_dir, "counts", program,
                           required=False)


class Window:
    """The handle a driver drives: ``serve(k)`` sends the next ``k``
    requests of the stream to the engine and waits for all of them;
    ``over()`` says whether the window's time is up."""

    def __init__(self, cellrun: "CellRun", deadline: float):
        self._c = cellrun
        self.deadline = deadline

    def over(self) -> bool:
        return time.perf_counter() >= self.deadline

    def serve(self, k: int) -> None:
        self._c.serve(k)


class CellRun:
    """Set-up, window and check of one run; ``finish()`` gives the line."""

    def __init__(self, root: Path, name: str, seed: int, seconds: float,
                 trace: bool, *, t_process: float, platform: str | None):
        self.cell = resolve(Path(root), name)
        self.run = Run(cell=self.cell, seed=int(seed), seconds=float(seconds),
                       trace=bool(trace))
        self.t_process = t_process
        self.platform = platform
        self.checked: list[Checked] = []
        self.attempted = 0
        self._profiling = None          # (t_lo, t_anchor) while on
        self._trace_lo = self._trace_hi = None
        self._work = self.cell.bench_dir / ".work"

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        import jax

        devs = jax.devices()
        dev = devs[0]
        if self.platform is not None:
            if dev.platform != self.platform:
                raise BenchError(f"jax.devices()[0] is {dev.platform!r} "
                                 f"({dev.device_kind}), not {self.platform!r}")
            if len(devs) < self.cell.chips:
                raise BenchError(f"{len(devs)} chips, the cell asks for "
                                 f"{self.cell.chips}")
            self.run.peaks = peaks_for(self.cell.bench_dir, dev.device_kind)
        self.device = dev
        self.n_devices = min(len(devs), self.cell.chips)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

        from repro.core.streams import StreamedRunner
        from repro.core.stream_config import SINGLE_STREAM
        from repro.core.workloads import get_workload
        from repro.serving.observability import Tracer

        cfg, tr = self.cell.config, self.cell.traffic
        self.tracer = Tracer() if self.run.trace else None
        self.engine = self._engine(self.tracer)
        self._stamp_pops(self.engine)

        self.buckets = self._make_data()
        views = {b: (datagen.request_view(ch, 0, b[1]), sh)
                 for b, (ch, sh) in self.buckets.items()}
        _ = self.engine.parallel_capacity     # the probe run() would make
        tenants = [f"tenant-{i}" for i in range(int(tr["tenants"]))]
        if tr["tuning_cache"] == "filled":
            who = tenants if tr["isolate_tenants"] else tenants[:1]
            self._serve_once(self.engine, [(t, b) for t in who
                                           for b in views], views)
        elif tr["tuning_cache"] == "empty":
            # a throwaway engine cold-tunes every bucket, each under a
            # tenant of its own: buckets whose row counts round to one
            # cache bucket would otherwise share one feature extraction,
            # and the others' shapes would compile in the window
            scratch = self._engine(None, isolate=True)
            self._serve_once(scratch, [(f"warm-{i}", b)
                                       for i, b in enumerate(views)], views)
            scratch.close()
        else:
            raise BenchError(f"tuning_cache {tr['tuning_cache']!r}")
        # every chunk shape a candidate split can give, so that a cold
        # tune or a drift refinement in the window compiles nothing: a
        # split's kernel calls take floor or ceil of rows / (partitions *
        # tasks) rows, and each such shape is warmed by one single-stream
        # dispatch of that many rows
        backend = cfg["engine"]["backend"]
        qs = {c.partitions * c.tasks for c in self.engine.candidates}
        for (prog, rows), (ch, sh) in views.items():
            sizes = {f(rows / q) for q in qs if q <= rows
                     for f in (math.floor, math.ceil)}
            for m in sorted(sizes):
                StreamedRunner(get_workload(prog),
                               datagen.request_view(ch, 0, m), sh,
                               backend=backend).warmup(SINGLE_STREAM)
        if self.run.trace:
            self.run.buckets = views
            for m in self.cell.per_layer:
                mod = load_plugin(self.cell.bench_dir, "metrics", m["name"])
                if hasattr(mod, "setup"):
                    mod.setup(self.run)
            self.run.buckets = {}
            shutil.rmtree(self._work, ignore_errors=True)
            self._work.mkdir(parents=True)
        self.items = trafficgen.request_items(cfg, tr, self.run.seed)
        # set-up's objects leave the collector's generations, so that a
        # full collection in the window does not walk them
        gc.collect()
        gc.freeze()

    def _engine(self, tracer, isolate: bool | None = None):
        from repro.core.modeling.heuristic import OverlapHeuristicModel
        from repro.serving.engine import ConcurrentScheduler
        from repro.serving.refinement import DriftDetector

        eng = self.cell.config["engine"]
        if eng["model"] != "heuristic":
            raise BenchError(f"model {eng['model']!r} is not supported")
        # "drift": "off" is a detector that never fires: no refinement
        # re-profiles in the window; the engine's own detector otherwise
        drift = {"on": None, "off": DriftDetector(threshold=math.inf)}[
            eng.get("drift", "on")]
        return ConcurrentScheduler(
            OverlapHeuristicModel(), window=int(eng["window"]),
            workers=int(eng["workers"]), backend=eng["backend"],
            isolate_tenants=bool(self.cell.traffic["isolate_tenants"]
                                 if isolate is None else isolate),
            drift=drift, tracer=tracer)

    def _stamp_pops(self, engine) -> None:
        """Stamp each request when the engine takes it off its queue, on
        the harness's clock (the engine's clock is the same
        ``perf_counter``)."""
        self.popped: dict = {}
        pop = engine.queue.pop

        def stamped():
            req = pop()
            self.popped[req.seq] = time.perf_counter()
            return req

        engine.queue.pop = stamped

    def _make_data(self) -> dict:
        progs = self.cell.config["programs"]
        with ThreadPoolExecutor(datagen.THREADS) as pool:
            return {(p, rows): datagen.bucket_data(p, spec, rows,
                                                   self.run.seed, pool)
                    for p, spec in sorted(progs.items())
                    for rows in spec["rows"]}

    def _serve_once(self, engine, who: list, views: dict) -> None:
        """Serve one request of each ``(tenant, bucket)`` in ``who``;
        the picks of the window's engine are kept for the readers."""
        from repro.serving.queue import WorkloadRequest

        reqs = {}
        for t, bucket in who:
            ch, sh = views[bucket]
            req = WorkloadRequest(bucket[0], ch, sh, tenant=t)
            reqs[id(req)] = bucket
            engine.submit(req)
        for r in engine.run():
            if engine is self.engine:
                self.run.picks[reqs[id(r.request)]] = (r.config.partitions,
                                                      r.config.tasks)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.run.compile_times.append(time.perf_counter())

    def _on_gc(self, phase: str, info: dict) -> None:
        """Each garbage collection of the window: (generation, seconds)."""
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            self.gc_pauses.append((info["generation"],
                                   time.perf_counter() - self._gc_t))

    # -- the window -----------------------------------------------------------

    def window(self) -> None:
        tr = self.cell.traffic
        driver = load_plugin(self.cell.bench_dir, "drivers", tr["driver"])
        t0 = time.perf_counter()
        self.run.setup_s = t0 - self.t_process
        self.run.t_start = t0
        if self.tracer is not None:
            self.tracer.clear()
        self._trace_from = t0 + TRACE_AFTER_S
        self.gc_pauses: list = []
        gc.callbacks.append(self._on_gc)
        driver.drive(Window(self, t0 + self.run.seconds), tr)
        if self._profiling is not None:
            self._stop_profile(time.perf_counter())
        self.run.t_end = time.perf_counter()
        gc.callbacks.remove(self._on_gc)
        if self.tracer is not None:
            self.run.spans = [s for s in self.tracer.spans
                              if s.t_start >= self.run.t_start]

    def serve(self, k: int) -> None:
        from repro.serving.queue import WorkloadRequest

        batch = {}
        for _ in range(k):
            it = next(self.items)
            ch, sh = self.buckets[(it.program, it.rows)]
            req = WorkloadRequest(it.program,
                                  datagen.request_view(ch, it.offset, it.rows),
                                  sh, tenant=it.tenant)
            batch[id(req)] = it
            self.engine.submit(req)
        self.attempted += k
        now = time.perf_counter()
        if (self.run.trace and self._profiling is None
                and self._trace_lo is None and now >= self._trace_from):
            self._start_profile()
        traced = self._profiling is not None
        results = self.engine.run()
        now = time.perf_counter()
        if traced and now - self._profiling[0] >= TRACE_FOR_S:
            self._stop_profile(now)
        for r in results:
            it = batch[id(r.request)]
            ok = r.status in ("served", "degraded")
            split = ((r.config.partitions, r.config.tasks)
                     if r.config is not None else (0, 0))
            self.run.done.append(Done(
                program=it.program, rows=it.rows, tenant=it.tenant,
                split=split, t_pop=self.popped.pop(r.request.seq, math.nan),
                t_retire=r.sample.t_retire_s,
                in_bytes=_nbytes(r.request.chunked) + _nbytes(r.request.shared),
                out_bytes=sum(int(o.nbytes) for o in r.outputs),
                ok=ok, traced=traced))
            if it.check:
                self.checked.append(Checked(
                    item=it, split=split,
                    outputs=[np.asarray(o) for o in r.outputs]))

    def _start_profile(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        lo = time.perf_counter()
        jax.profiler.start_trace(str(self._work), profiler_options=opts)
        with jax.profiler.TraceAnnotation(devtrace.ANCHOR):
            anchor = time.perf_counter()
        self._profiling = (lo, anchor)

    def _stop_profile(self, hi: float) -> None:
        import jax

        jax.profiler.stop_trace()
        self._trace_lo, self._anchor = self._profiling
        self._trace_hi = hi
        self._profiling = None

    # -- after the window -----------------------------------------------------

    def finish(self) -> dict:
        """Memory peak, then the program's state freed, then the trace,
        the check and the metrics; returns the result line."""
        import jax

        stats = self.device.memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        self.engine.close()
        self.engine = None
        gc.unfreeze()
        gc.collect()

        breakdown = None
        if self.run.trace and self._trace_lo is not None:
            path = devtrace.find_xplane(str(self._work))
            events = devtrace.load(path) if path else None
            if events and events["anchor_ns"] is not None and events["ops"]:
                to_ns = self._to_ns(events["anchor_ns"])
                red = devtrace.reduce(events, to_ns(self._trace_lo),
                                      to_ns(self._trace_hi))
                self.run.device = red
                top = sorted(red["op_totals_s"].items(),
                             key=lambda kv: -kv[1])[:10]
                breakdown = {
                    "device_ops": [[k, v] for k, v in top],
                    "idle_gaps": devtrace.name_gaps(red["gaps_ns"],
                                                    self.run.spans, to_ns)}
            shutil.rmtree(self._work, ignore_errors=True)

        t_check = time.perf_counter()
        ok, checks = self.correctness()
        full = [t for g, t in self.gc_pauses if g == 2]
        print(f"bench: setup {self.run.setup_s:.1f} s, window "
              f"{self.run.t_end - self.run.t_start:.1f} s, "
              f"{len(self.run.done)} served, {len(self.checked)} checked "
              f"in {time.perf_counter() - t_check:.1f} s; garbage "
              f"collections in the window {len(self.gc_pauses)} "
              f"({sum(t for _, t in self.gc_pauses):.4f} s), full "
              f"{len(full)} ({sum(full):.4f} s, longest "
              f"{max(full, default=0.0):.4f} s)", file=sys.stderr)
        failed = sum(1 for d in self.run.done if not d.ok) \
            + (self.attempted - len(self.run.done))
        metrics = {}
        for m in (self.cell.per_layer if self.run.trace
                  else self.cell.end_to_end):
            mod = load_plugin(self.cell.bench_dir, "metrics", m["name"])
            value = mod.read(self.run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = {"platform": self.device.platform,
                  "kind": self.device.device_kind,
                  "count": self.n_devices, "memory_peak_bytes": peak}
        if self.run.device is not None:
            device["busy_s"] = self.run.device["busy_s"]
            device["window_s"] = self.run.device["window_s"]
        line = {"correct": ok and failed == 0, "attempted": self.attempted,
                "failed": failed, "metrics": metrics, "device": device}
        if breakdown is not None:
            line["breakdown"] = breakdown
        line["checks"] = checks
        return line

    def _to_ns(self, anchor_ns: float):
        anchor = self._anchor
        return lambda t: anchor_ns + (t - anchor) * 1e9

    def gaps(self, outputs_of=None) -> dict:
        """Program -> largest gap over its checked requests, the
        references computed on a few host threads.  ``outputs_of(checked,
        reference)`` may put other outputs in the program's place (the
        control does)."""
        progs = self.cell.config["programs"]
        refs = {p: load_plugin(self.cell.bench_dir, "reference", p)
                for p in {c.item.program for c in self.checked}}

        def one(c: Checked) -> tuple[str, float]:
            prog = c.item.program
            ch, sh = self.buckets[(prog, c.item.rows)]
            view = datagen.request_view(ch, c.item.offset, c.item.rows)
            outs = c.outputs if outputs_of is None \
                else outputs_of(c, refs[prog])
            return prog, check.request_gap(refs[prog],
                                           progs[prog]["combine"], view, sh,
                                           c.split, outs)

        gaps: dict = {}
        with ThreadPoolExecutor(CHECK_THREADS) as pool:
            for prog, g in pool.map(one, self.checked):
                gaps[prog] = max(gaps.get(prog, 0.0), g)
        return gaps

    def correctness(self) -> tuple[bool, dict]:
        return check.verdict(self.gaps(), self.cell.config["limits"])


def _nbytes(d: dict) -> int:
    return sum(int(a.nbytes) for a in d.values())


def peaks_for(bench_dir: Path, kind: str) -> dict:
    table = load_json(bench_dir / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"{bench_dir / 'peaks.json'}")
    return table[kind]


def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             *, t_process: float, platform: str | None = "tpu") -> dict:
    """One whole run; returns the result line's object."""
    cr = CellRun(root, name, seed, seconds, trace, t_process=t_process,
                 platform=platform)
    cr.setup()
    cr.window()
    return cr.finish()


def report(line: dict) -> None:
    """The compared numbers on stderr, then the result as the last line
    of stdout."""
    for prog, c in line["checks"].items():
        print(f"check {prog}: {c['value']} <= {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def point_compile_cache(bench_dir: Path) -> Path:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, for this process and the program (which takes
    ``JAX_COMPILATION_CACHE_DIR`` when it is set); compiles of any
    length are kept, so that only a cell's first run compiles."""
    path = bench_dir / ".jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
