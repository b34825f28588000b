"""One run of one benchmark cell: set-up, the measured window, the check
against the plain reference, and the metrics.

Everything that belongs to one configuration, traffic mix, driver or
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

  <bench>/configs/<config>.json     programs, sizes, engine, limits
  <bench>/engines/<kind>.py         ``open(config, traffic, ...)``: the
                                    serving engine the configuration's
                                    ``engine.kind`` names (``concurrent``
                                    when absent; ``engine_api``)
  <bench>/traffic/<mix>.json        the driver's name and parameters
  <bench>/drivers/<driver>.py       ``drive(window, traffic)``
  <bench>/metrics/<metric>.py       ``read(run) -> float | None``, and
                                    optionally ``setup(run)`` for the
                                    traced run's set-up
  <bench>/counts/<program>.py       ``counts(rows) -> (flops, bytes)``
  <bench>/reference/<program>.py    ``kernel(P, chunked, shared)``

The harness keeps what is the benchmark's: data, the request stream and
the window, the drivers, the check against the reference, the metric
readers and the result line.  It opens no chip itself: the engine says
which chips it serves on and hands back their memory peaks, traces and
the program's spans, so the chips may belong to other processes.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import check
import datagen
import devtrace
import engine_api
import trafficgen

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

    @property
    def engine_kind(self) -> str:
        return engine_api.kind_of(self.config)


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
    device: dict | None = None        # devtrace.combine of the chips'
    #                                   reductions of the sub-window
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
        self._profiling = None          # t_lo while on
        self._trace_lo = self._trace_hi = None
        self._work = self.cell.bench_dir / ".work"
        self._opener = load_plugin(self.cell.bench_dir, "engines",
                                   self.cell.engine_kind)
        self.engine = None

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        cfg, tr = self.cell.config, self.cell.traffic
        setups = []
        if self.run.trace:
            shutil.rmtree(self._work, ignore_errors=True)
            self._work.mkdir(parents=True)
            setups = [m["name"] for m in self.cell.per_layer
                      if hasattr(load_plugin(self.cell.bench_dir, "metrics",
                                             m["name"]), "setup")]
        if setups and not self._opener.OWN_CHIPS:
            raise BenchError(
                f"engine {self.cell.engine_kind!r} serves on chips of other "
                f"processes; metrics that set up on this process's chips "
                f"cannot read it: {setups}")
        self.engine = self._opener.open(cfg, tr, trace=self.run.trace,
                                        work=self._work)
        self.chips = self.engine.chips()
        if self.platform is not None:
            for i, c in enumerate(self.chips):
                if c.platform != self.platform:
                    raise BenchError(f"chip {i} is {c.platform!r} ({c.kind}),"
                                     f" not {self.platform!r}")
            if len(self.chips) != self.cell.chips:
                raise BenchError(f"{len(self.chips)} chips, the cell asks "
                                 f"for {self.cell.chips}")
            self.run.peaks = peaks_for(self.cell.bench_dir,
                                       self.chips[0].kind)

        self.buckets = self._make_data()
        views = {b: (datagen.request_view(ch, 0, b[1]), sh)
                 for b, (ch, sh) in self.buckets.items()}
        tenants = [f"tenant-{i}" for i in range(int(tr["tenants"]))]
        self.run.picks = self.engine.setup(views, tenants)
        if setups:
            self.run.buckets = views
            for name in setups:
                load_plugin(self.cell.bench_dir, "metrics", name).setup(
                    self.run)
            self.run.buckets = {}
        self.items = trafficgen.request_items(cfg, tr, self.run.seed)
        # set-up's objects leave the collector's generations, so that a
        # full collection in the window does not walk them
        gc.collect()
        gc.freeze()

    def _make_data(self) -> dict:
        progs = self.cell.config["programs"]
        with ThreadPoolExecutor(datagen.THREADS) as pool:
            return {(p, rows): datagen.bucket_data(p, spec, rows,
                                                   self.run.seed, pool)
                    for p, spec in sorted(progs.items())
                    for rows in spec["rows"]}

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
        self.engine.begin()
        self._trace_from = t0 + TRACE_AFTER_S
        self.gc_pauses: list = []
        gc.callbacks.append(self._on_gc)
        driver.drive(Window(self, t0 + self.run.seconds), tr)
        if self._profiling is not None:
            self._stop_profile(time.perf_counter())
        self.run.t_end = time.perf_counter()
        gc.callbacks.remove(self._on_gc)

    def serve(self, k: int) -> None:
        batch = []
        for key in range(k):
            it = next(self.items)
            ch, sh = self.buckets[(it.program, it.rows)]
            batch.append((it, engine_api.Request(
                key=key, program=it.program, tenant=it.tenant,
                chunked=datagen.request_view(ch, it.offset, it.rows),
                shared=sh, check=it.check)))
        self.attempted += k
        now = time.perf_counter()
        if (self.run.trace and self._profiling is None
                and self._trace_lo is None and now >= self._trace_from):
            self._profiling = time.perf_counter()
            self.engine.start_profile()
        traced = self._profiling is not None
        served = self.engine.serve([q for _, q in batch])
        now = time.perf_counter()
        if traced and now - self._profiling >= TRACE_FOR_S:
            self._stop_profile(now)
        for s in served:
            it = batch[s.key][0]
            self.run.done.append(Done(
                program=it.program, rows=it.rows, tenant=it.tenant,
                split=s.split, t_pop=s.t_pop, t_retire=s.t_retire,
                in_bytes=s.in_bytes, out_bytes=s.out_bytes, ok=s.ok,
                traced=traced))
            if it.check:
                self.checked.append(Checked(item=it, split=s.split,
                                            outputs=s.outputs))

    def _stop_profile(self, hi: float) -> None:
        self.engine.stop_profile()
        self._trace_lo, self._trace_hi = self._profiling, hi
        self._profiling = None

    # -- after the window -----------------------------------------------------

    def finish(self) -> dict:
        """The engine's spans, memory peaks and traces, then its state
        freed, then the device reduction, the check and the metrics;
        returns the result line."""
        got = self.engine.collect()
        self.close()
        gc.unfreeze()
        gc.collect()
        self.run.compile_times = got.compile_times
        self.run.spans = [s for s in got.spans
                          if s.t_start >= self.run.t_start]
        breakdown = None
        if self.run.trace:
            if self._trace_lo is not None:
                breakdown = self._reduce_traces(got.traces)
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
        device = {"platform": self.chips[0].platform,
                  "kind": self.chips[0].kind, "count": len(self.chips),
                  "memory_peak_bytes": max(got.memory_peaks, default=0)}
        if self.run.device is not None:
            device["busy_s"] = self.run.device["busy_s"]
            device["window_s"] = self.run.device["window_s"]
        line = {"correct": ok and failed == 0, "attempted": self.attempted,
                "failed": failed, "metrics": metrics, "device": device}
        if breakdown is not None:
            line["breakdown"] = breakdown
        line["checks"] = checks
        return line

    def close(self) -> None:
        """Stop the engine, and every process it started, if it is open."""
        engine, self.engine = self.engine, None
        if engine is not None:
            engine.close()

    def _reduce_traces(self, traces: list) -> dict | None:
        """Each chip's trace over the profiled sub-window, against its
        own anchor, combined into ``run.device``; returns the breakdown:
        the device ops that took most time over the chips, and the
        longest idle gaps of any chip, each named by the spans of the
        process that holds the chip.  Nothing when a trace has no
        anchor or no chip ran an op."""
        if (not traces or any(t.events["anchor_ns"] is None for t in traces)
                or not any(t.events["ops"] for t in traces)):
            return None
        reds, gaps = [], []
        for t in traces:
            to_ns = _to_ns(t.events["anchor_ns"], t.anchor)
            red = devtrace.reduce(t.events, to_ns(self._trace_lo),
                                  to_ns(self._trace_hi))
            reds.append(red)
            gaps += devtrace.name_gaps(
                red["gaps_ns"], [s for s in self.run.spans
                                 if s.proc == t.proc], to_ns)
        self.run.device = devtrace.combine(reds)
        top = sorted(self.run.device["op_totals_s"].items(),
                     key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:10]}

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


def _to_ns(anchor_ns: float, anchor: float):
    """Host-clock seconds to a trace's nanoseconds, through an instant
    both clocks stamped."""
    return lambda t: anchor_ns + (t - anchor) * 1e9


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
    try:
        cr.setup()
        cr.window()
        return cr.finish()
    finally:
        cr.close()


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
