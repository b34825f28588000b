"""End-to-end observability: span tracing and the metrics registry
(repro.serving.observability).

Covers the PR's acceptance bars: spans nest correctly under a virtual
clock, trace IDs survive the concurrent engine's out-of-order
retirement, two seeded replays produce byte-identical metrics
snapshots, the Chrome export is valid trace-event JSON, the disabled
path is a shared no-op singleton (zero per-call allocation), the
empty-window telemetry contract (typed raise at the primitive, None at
the aggregators), and one-clock plumbing across queue / scheduler /
refiner / tracer."""
import collections
import json
import time

import pytest

from repro.core.backends import no_span
from repro.core.backends.base import NO_SPAN
from repro.core.stream_config import StreamConfig
from repro.serving import (AdaptiveScheduler, ConcurrentScheduler,
                           NULL_METRICS, NULL_TRACER, MetricsRegistry,
                           OverlapHeuristicModel, TelemetryLog, Tracer,
                           make_trace)
from repro.serving.clock import VirtualClock
from repro.serving.observability.metrics import (_NULL_INSTRUMENT,
                                                 Histogram)
from repro.serving.observability.tracing import stage_of
from repro.serving.telemetry import (EmptyWindowError, TelemetrySample,
                                     latency_stats, percentile)
from repro.serving.traces import TraceConfig, generate_trace, \
    simulate_trace


def _sched(model=None, **kw):
    kw.setdefault("telemetry", TelemetryLog())
    kw.setdefault("keep_outputs", False)
    return AdaptiveScheduler(model or OverlapHeuristicModel(), **kw)


# -- span tracing ------------------------------------------------------------


def test_spans_nest_under_virtual_clock():
    clock = VirtualClock()
    tr = Tracer(clock)
    with tr.span("retire", trace_id="r000000"):
        clock.advance(1.0)
        with tr.span("refine", trace_id="r000000", key="k"):
            clock.advance(2.0)
        clock.advance(0.5)
    inner, outer = tr.spans        # exit order: inner closes first
    assert inner.name == "refine" and outer.name == "retire"
    assert inner.parent == "retire" and inner.depth == 1
    assert outer.parent is None and outer.depth == 0
    assert inner.t_start == 1.0 and inner.t_end == 3.0
    assert outer.t_start == 0.0 and outer.t_end == 3.5
    assert inner.duration_s == pytest.approx(2.0)
    assert inner.attrs == {"key": "k"}


def test_stage_of_rollup():
    assert stage_of("tune.cold.batch") == "tune"
    assert stage_of("decide") == "decide"
    assert stage_of("custom") == "custom"


def test_trace_ids_survive_out_of_order_retirement():
    tr = Tracer()
    sched = ConcurrentScheduler(
        OverlapHeuristicModel(), window=3, tracer=tr,
        telemetry=TelemetryLog(), keep_outputs=False)
    trace = make_trace(["vecadd", "dotprod"], occurrences=3)
    with sched:
        submitted = [sched.submit(r).trace_id for r in trace]
        results = sched.run()
    assert submitted == [f"r{i:06d}" for i in range(len(trace))]
    # every result's telemetry sample carries its OWN request's id, even
    # though the engine retires buckets out of order
    for r in results:
        assert r.sample.trace_id == r.request.trace_id
    assert {s.trace_id for s in sched.telemetry} == set(submitted)
    # spans correlate by the same ids
    span_ids = {s.trace_id for s in tr.spans if s.trace_id}
    assert span_ids == set(submitted)


def test_chrome_export_is_valid_trace_event_json(tmp_path):
    clock = VirtualClock()
    tr = Tracer(clock)
    with tr.span("decide", trace_id="r000000", tenant="acme"):
        clock.advance(0.25)
    tr.record("dispatch", 0.25, 0.75, trace_id="r000000", tid=1)
    path = tmp_path / "trace.json"
    assert tr.export_chrome(str(path)) == 2
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == 2
    for e in xs:
        assert set(e) >= {"name", "cat", "ph", "ts", "dur", "pid", "tid"}
        assert e["ts"] >= 0 and e["dur"] >= 0         # rebased, us
    assert xs[0]["args"]["trace_id"] == "r000000"
    assert {e["tid"] for e in xs} == {0, 1}
    # metadata record names the process for the Perfetto track header
    assert events[0]["ph"] == "M"


def test_jsonl_export_roundtrip(tmp_path):
    tr = Tracer(VirtualClock())
    tr.record("retire", 1.0, 2.0, trace_id="r000003", load=1.5)
    path = tmp_path / "spans.jsonl"
    assert tr.export_jsonl(str(path)) == 1
    d = json.loads(path.read_text().strip())
    assert d == {"name": "retire", "t_start": 1.0, "t_end": 2.0,
                 "tid": 0, "trace_id": "r000003",
                 "attrs": {"load": 1.5}}


def test_null_tracer_is_shared_noop():
    # the hot-path contract: one shared span object, nothing recorded,
    # no clock reads — schedulers built without a tracer pay ~nothing
    s1 = NULL_TRACER.span("decide", trace_id="r000000", tenant="a")
    s2 = NULL_TRACER.span("dispatch")
    assert s1 is s2 is NO_SPAN
    with s1:
        pass
    NULL_TRACER.record("retire", 0.0, 1.0)
    assert len(NULL_TRACER) == 0 and NULL_TRACER.spans == []
    assert not NULL_TRACER.enabled


def test_scheduler_never_mutates_null_singletons():
    sched = _sched(clock=VirtualClock())
    assert sched.tracer is NULL_TRACER
    assert sched.metrics is NULL_METRICS
    assert NULL_TRACER.clock is None       # bind-my-clock must not leak


# -- phase spans: dispatch, cold tunes, pool drains --------------------------


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each enter
    and exit by name."""

    def __init__(self, log):
        self.log = log

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return _Ann()


class _CountingClock:
    def __init__(self, log=None):
        self.reads = 0
        self.log = log

    def now(self):
        self.reads += 1
        if self.log is not None:
            self.log.append(("now", None))
        return time.perf_counter()


#: one split with more tasks than host-pipelined's window of 2, so the
#: backend retires tasks inside its own dispatch
SPLIT = StreamConfig(1, 4)


def _engine(tracer, window, **kw):
    return ConcurrentScheduler(
        OverlapHeuristicModel(), window=window, backend="host-pipelined",
        candidates=[SPLIT], tracer=tracer, telemetry=TelemetryLog(),
        keep_outputs=False, **kw)


def _children(spans, outer):
    """The spans nested directly in ``outer``: same thread, one level
    deeper, inside its interval."""
    return [s for s in spans if s.tid == outer.tid
            and s.depth == outer.depth + 1
            and outer.t_start <= s.t_start <= s.t_end <= outer.t_end]


def test_dispatch_phases_nest_in_each_dispatch_span():
    tr = Tracer()
    programs = ["vecadd", "dotprod", "mvmult", "scalarprod"]
    with _engine(tr, window=3) as sched:
        sched.submit_all(make_trace(programs, occurrences=1))
        results = sched.run()
    assert [r.config for r in results] == [SPLIT] * len(programs)
    coord = {s.tid for s in tr.spans if s.name == "decide"}
    dispatches = [s for s in tr.spans if s.name == "dispatch"]
    assert len(dispatches) == len(programs)
    for d in dispatches:
        assert d.tid not in coord and d.parent is None
        kids = _children(tr.spans, d)
        assert all(k.parent == "dispatch" for k in kids)
        names = collections.Counter(k.name for k in kids)
        # every key is new: one warm-up, then issue, wait and read
        assert names == {"dispatch.warmup": 1, "dispatch.issue": 1,
                         "dispatch.wait": 1, "dispatch.read": 1}
        order = [k.name for k in sorted(kids, key=lambda k: k.t_start)]
        assert order == ["dispatch.warmup", "dispatch.issue",
                         "dispatch.wait", "dispatch.read"]
        (issue,) = [k for k in kids if k.name == "dispatch.issue"]
        window_waits = _children(tr.spans, issue)
        # 4 tasks through a window of 2: tasks 1-3 retire inside issue
        assert [w.name for w in window_waits] == ["dispatch.wait"] * 3
        assert all(w.parent == "dispatch.issue" for w in window_waits)
    # phases are spans of their own: one retire per request still
    names = collections.Counter(s.name for s in tr.spans)
    assert names["retire"] == len(programs)


def test_dispatch_warmup_only_on_first_dispatch_of_a_key():
    tr = Tracer()
    with _engine(tr, window=1) as sched:
        sched.submit_all(make_trace(["vecadd", "mvmult"], occurrences=3,
                                    tenants=("a",)))
        results = sched.run()
    program = {r.request.trace_id: r.request.workload for r in results}
    seen = set()
    for d in sorted((s for s in tr.spans if s.name == "dispatch"),
                    key=lambda s: s.t_start):
        # one tenant, one shape a program: the program names the key,
        # and a drift refinement may change the split
        key = (program[d.trace_id], d.attrs["partitions"], d.attrs["tasks"])
        warm = [k for k in _children(tr.spans, d)
                if k.name == "dispatch.warmup"]
        assert len(warm) == (key not in seen), key
        seen.add(key)
    assert len(seen) < len(results)


def test_cold_tunes_hold_one_static_and_one_profile_per_bucket():
    tr = Tracer()
    programs = ["vecadd", "dotprod", "mvmult"]
    with _engine(tr, window=4) as sched:
        sched.submit_all(make_trace(programs, occurrences=1))
        sched.run()
    (batch,) = [s for s in tr.spans if s.name == "tune.cold.batch"]
    assert batch.attrs["buckets"] == len(programs)
    kids = collections.Counter(k.name for k in _children(tr.spans, batch))
    assert kids == {"tune.static": 3, "tune.profile": 3}
    # the serial scheduler's one-bucket tune holds one of each
    tr = Tracer()
    with _sched(tracer=tr) as sched:
        sched.submit_all(make_trace(["vecadd"], occurrences=1))
        sched.run()
    (cold,) = [s for s in tr.spans if s.name == "tune.cold"]
    kids = _children(tr.spans, cold)
    assert [k.name for k in kids] == ["tune.static", "tune.profile"]
    assert all(k.parent == "tune.cold" for k in kids)


def test_cold_wave_opens_engine_drain_on_the_coordinator():
    tr = Tracer()
    with _engine(tr, window=2) as sched:
        sched.submit_all(make_trace(["vecadd", "dotprod", "mvmult"],
                                    occurrences=1))
        sched.run()
    coord = {s.tid for s in tr.spans if s.name == "decide"}
    drains = [s for s in tr.spans if s.name == "engine.drain"]
    # two waves, each holding a cold request
    assert [d.attrs for d in drains] == [{"why": "cold"}] * 2
    assert {d.tid for d in drains} == coord and len(coord) == 1
    assert all(d.parent is None for d in drains)
    # a warm hit persisted by another process drains for its anchor
    cache = sched.cache
    tr = Tracer()
    with _engine(tr, window=2, cache=cache) as sched:
        sched.submit_all(make_trace(["vecadd"], occurrences=1))
        sched.run()
    assert [s.attrs for s in tr.spans if s.name == "engine.drain"] \
        == [{"why": "anchor"}]


def test_null_tracer_dispatch_reads_no_clock_and_enters_no_annotation(
        monkeypatch):
    from repro.serving.observability import tracing

    log = []
    monkeypatch.setattr(tracing, "TraceAnnotation", _Annotations(log))

    def one_dispatch(tracer):
        clock = _CountingClock()
        sched = AdaptiveScheduler(
            OverlapHeuristicModel(), backend="host-pipelined",
            candidates=[SPLIT], clock=clock, tracer=tracer,
            telemetry=TelemetryLog(), keep_outputs=False)
        (req,) = make_trace(["vecadd"], occurrences=1)
        sched.submit(req)
        pending = sched._decide(sched.queue.pop())
        sched._tune_cold(pending)
        factories = []
        dispatch = pending.runner.dispatch

        def spy(config, *, span=no_span):
            factories.append(span)
            return dispatch(config, span=span)

        pending.runner.dispatch = spy
        before = clock.reads
        sched._execute(pending)
        return clock.reads - before, factories

    del log[:]
    reads, factories = one_dispatch(NULL_TRACER)
    # the scheduler's own stamps (dispatch time, measured start and end)
    # and nothing else; the backend gets the shared no-op factory
    assert reads == 3 and log == []
    assert factories == [no_span] and no_span("dispatch.wait") is NO_SPAN

    reads, factories = one_dispatch(Tracer())
    assert reads > 3 and log
    assert factories[0] is not no_span


def test_mirror_enters_and_exits_one_annotation_per_span(monkeypatch):
    from repro.serving.observability import tracing

    log = []
    monkeypatch.setattr(tracing, "TraceAnnotation", _Annotations(log))
    tr = Tracer(_CountingClock(log))
    with tr.span("dispatch", trace_id="r000000", tasks=4):
        with tr.span("dispatch.issue"):
            pass
    # each annotation opens before its span's first clock read and
    # closes after its last, nested as the spans are
    assert log == [("enter", "dispatch"), ("now", None),
                   ("enter", "dispatch.issue"), ("now", None),
                   ("now", None), ("exit", "dispatch.issue"),
                   ("now", None), ("exit", "dispatch")]
    assert [s.name for s in tr.spans] == ["dispatch.issue", "dispatch"]


# -- metrics registry --------------------------------------------------------


def test_null_metrics_shared_instrument():
    c = NULL_METRICS.counter("serving.requests")
    g = NULL_METRICS.gauge("serving.queue.depth", tenant="acme")
    h = NULL_METRICS.histogram("serving.stage.decide.seconds")
    assert c is g is h is _NULL_INSTRUMENT
    c.inc(); g.set(3); h.observe(0.1)      # all no-ops
    assert NULL_METRICS.snapshot() == {}
    assert not NULL_METRICS.enabled


def test_registry_get_or_create_and_kind_confusion():
    m = MetricsRegistry()
    a = m.counter("serving.requests")
    assert m.counter("serving.requests") is a
    b = m.counter("serving.cache.hit", namespace="acme")
    assert m.counter("serving.cache.hit", namespace="globex") is not b
    with pytest.raises(TypeError):
        m.gauge("serving.requests")


def test_histogram_buckets_and_stats():
    h = Histogram(buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(5.555)
    assert snap["min"] == 0.005 and snap["max"] == 5.0
    assert snap["buckets"] == {"0.01": 1, "0.1": 1, "1.0": 1, "+Inf": 1}


def test_prometheus_text_format():
    m = MetricsRegistry()
    m.counter("serving.requests").inc(3)
    m.counter("serving.cache.hit", namespace="acme").inc()
    m.histogram("serving.stage.decide.seconds",
                buckets=(0.1, 1.0)).observe(0.05)
    text = m.to_prometheus()
    assert "# TYPE serving_requests counter" in text
    assert "serving_requests 3" in text
    assert 'serving_cache_hit{namespace="acme"} 1' in text
    # histogram series: cumulative buckets + sum + count
    assert 'serving_stage_decide_seconds_bucket{le="0.1"} 1' in text
    assert 'serving_stage_decide_seconds_bucket{le="+Inf"} 1' in text
    assert "serving_stage_decide_seconds_count 1" in text


def test_metrics_snapshot_deterministic_across_replays():
    def run():
        m = MetricsRegistry()
        tr = Tracer()
        cfg = TraceConfig(n_requests=400, seed=7,
                          workloads=("vecadd", "dotprod"),
                          arrival="bursty")
        report = simulate_trace(generate_trace(cfg), policy="deadline",
                                seed=7, tracer=tr, metrics=m)
        return report, m.snapshot(), [s.to_json() for s in tr.spans]

    r1, snap1, spans1 = run()
    r2, snap2, spans2 = run()
    assert snap1 == snap2
    assert spans1 == spans2
    assert snap1["serving.requests"]["values"][0]["value"] \
        == r1["completed"]
    assert snap1["serving.queue.shed"]["values"][0]["value"] == r1["shed"]
    hit = snap1["serving.cache.hit"]["values"][0]["value"]
    miss = snap1["serving.cache.miss"]["values"][0]["value"]
    assert hit + miss == r1["completed"]
    assert miss == r1["cold_misses"]


def test_sim_spans_cover_stages_and_clock_is_virtual():
    tr = Tracer()
    cfg = TraceConfig(n_requests=50, seed=1, workloads=("vecadd",),
                      slo_choices=None)
    simulate_trace(generate_trace(cfg), policy="fifo", tracer=tr)
    names = {stage_of(s.name) for s in tr.spans}
    assert {"decide", "tune", "dispatch", "retire"} <= names
    # virtual timeline: all stamps inside the trace's virtual horizon,
    # far below any perf_counter reading
    assert all(0.0 <= s.t_start <= s.t_end < 1e4 for s in tr.spans)


# -- telemetry empty-window contract -----------------------------------------


def test_percentile_empty_raises_typed():
    with pytest.raises(EmptyWindowError) as ei:
        percentile([], 0.5)
    assert "empty window" in str(ei.value)
    assert isinstance(ei.value, ValueError)     # back-compat catch sites


def test_latency_stats_and_summary_empty_return_none():
    assert latency_stats([]) is None
    s = TelemetryLog().summary()                # nothing ever retired
    assert s["requests"] == 0
    assert s["latency"] is None
    assert s["hit_rate"] == 0.0
    assert s["slo_violation_rate"] is None
    assert s["mean_rel_error"] is None
    assert s["per_tenant"] == {}


def test_summary_when_every_request_shed():
    # deadline queue sheds the whole trace -> zero samples, but both the
    # scheduler summary path and the sim report must still render
    clock = VirtualClock()
    sched = _sched(policy="deadline", clock=clock)
    trace = make_trace(["vecadd"], occurrences=2)
    for req in trace:
        req.deadline_s = -1.0                   # expired before submit
    sched.submit_all(trace)
    assert sched.run() == []
    assert len(sched.queue.shed) == len(trace)
    s = sched.telemetry.summary()
    assert s["requests"] == 0 and s["latency"] is None


# -- one clock everywhere ----------------------------------------------------


def test_clock_plumbed_to_every_component():
    clock = VirtualClock()
    tr = Tracer()
    sched = _sched(clock=clock, tracer=tr, metrics=MetricsRegistry())
    assert sched.clock is clock
    assert sched.queue.clock is clock
    assert sched.refiner.clock is clock
    assert sched.tracer.clock is clock


def test_explicit_tracer_clock_is_respected():
    mine = VirtualClock()
    tr = Tracer(mine)
    sched = _sched(tracer=tr)
    assert tr.clock is mine                     # not rebound


# -- live schedulers: spans + metrics on the real path -----------------------


def test_serial_scheduler_metrics_and_spans_consistent():
    tr = Tracer()
    m = MetricsRegistry()
    sched = _sched(tracer=tr, metrics=m)
    with sched:
        sched.submit_all(make_trace(["vecadd", "dotprod"], occurrences=2))
        results = sched.run()
    n = len(results)
    snap = m.snapshot()

    def val(name):
        return snap[name]["values"][0]["value"]

    assert val("serving.requests") == n
    hits = sum(e["value"] for e in snap["serving.cache.hit"]["values"])
    misses = sum(e["value"] for e in snap["serving.cache.miss"]["values"])
    assert hits + misses == n
    assert misses == sum(not r.cache_hit for r in results)
    assert val("serving.model.searches") == sched.stats["model_searches"]
    for stage in ("decide", "dispatch", "retire"):
        assert val(f"serving.stage.{stage}.seconds")["count"] == n
    # one top-level decide/dispatch/retire span per request
    top = collections.Counter(s.name for s in tr.spans if s.depth == 0)
    assert top["decide"] == top["dispatch"] == top["retire"] == n
    # telemetry carries the queue-assigned ids
    assert all(s.trace_id is not None for s in sched.telemetry)


def test_engine_batched_tune_records_batch_size():
    m = MetricsRegistry()
    sched = ConcurrentScheduler(
        OverlapHeuristicModel(), window=4, metrics=m,
        telemetry=TelemetryLog(), keep_outputs=False)
    with sched:
        sched.submit_all(make_trace(["vecadd", "dotprod", "mvmult"],
                                    occurrences=1))
        sched.run()
    snap = m.snapshot()
    batch = snap["serving.cold_batch.size"]["values"][0]["value"]
    assert batch["count"] >= 1
    assert batch["max"] >= 2                   # >=2 cold buckets batched


# -- stats CLI ---------------------------------------------------------------


def test_stats_render_smoke():
    from repro.launch.stats import render
    samples = [TelemetrySample(
        seq=i, tenant="acme", workload="vecadd", key="k",
        backend="host-sync", partitions=1, tasks=2, cache_hit=i > 0,
        predicted_s=1e-3, measured_s=1.1e-3, rel_error=0.1,
        latency_s=2e-3, trace_id=f"r{i:06d}") for i in range(3)]
    m = MetricsRegistry()
    m.counter("serving.requests").inc(3)
    m.histogram("serving.stage.decide.seconds").observe(1e-4)
    out = render(samples, m.snapshot())
    assert "requests 3" in out
    assert "hit_rate 0.67" in out
    assert "p95" in out
    assert "serving.requests" in out
    assert "serving.stage.decide.seconds" in out


def test_stats_render_empty_samples():
    from repro.launch.stats import render
    out = render([])
    assert "no retired requests" in out
