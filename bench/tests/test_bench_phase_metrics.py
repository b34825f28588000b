"""The readers of the program's phase spans, on hand-built runs: each
reads what its definition says, reads nothing from a program without
the phases, and the readers that were there before read the same with
the phases added.  Then the tiny cell's traced run on the CPU: the
dispatch phases add up to the ``dispatch`` wall."""
import time

import pytest

import harness
from bench_testkit import tiny_copy
from repro.serving.observability.tracing import SpanRecord

PHASE_METRICS = ("dispatch_issue_ms", "dispatch_wait_ms", "dispatch_read_ms",
                 "dispatch_warmup_ms", "tune_static_ms", "tune_profile_ms",
                 "drain_share")
OLD_METRICS = ("coordinator_share", "exec_gb_s", "tune_ms_per_cold")
COORD, WORKER = 0, 1


def reader(name):
    return harness.load_plugin(harness.Path(__file__).resolve().parents[1],
                               "metrics", name)


def span(name, t0, t1, tid, parent=None, depth=0, **attrs):
    return SpanRecord(name=name, t_start=t0, t_end=t1, tid=tid,
                      parent=parent, depth=depth, attrs=attrs or None)


def stages():
    """The spans a program without phases records: two requests, one
    cold wave of a batch of two buckets, on a window of 10 s."""
    return [
        span("decide", 0.0, 0.1, COORD),
        span("decide", 0.1, 0.2, COORD),
        span("tune.cold.batch", 0.3, 1.3, COORD, buckets=2, requests=2),
        span("dispatch", 2.0, 3.0, WORKER, partitions=1, tasks=4),
        span("retire", 3.1, 3.2, COORD),
        span("decide", 4.0, 4.1, COORD),
        span("tune.cold", 4.2, 4.8, COORD),
        span("dispatch", 5.0, 5.6, WORKER, partitions=1, tasks=1),
        span("retire", 6.0, 6.2, COORD),
        span("retire", 7.0, 7.2, COORD),
    ]


def phases():
    """What the program now nests in those spans."""
    d, t = "dispatch", "dispatch.issue"
    return [
        # request 1: warm-up 0.2, issue 0.5 holding two window waits of
        # 0.1, final wait 0.1, read 0.1
        span("dispatch.warmup", 2.0, 2.2, WORKER, d, 1),
        span("dispatch.issue", 2.2, 2.7, WORKER, d, 1),
        span("dispatch.wait", 2.3, 2.4, WORKER, t, 2),
        span("dispatch.wait", 2.5, 2.6, WORKER, t, 2),
        span("dispatch.wait", 2.7, 2.8, WORKER, d, 1),
        span("dispatch.read", 2.8, 2.9, WORKER, d, 1),
        # request 2: issue 0.3, wait 0.1, read 0.1
        span("dispatch.issue", 5.0, 5.3, WORKER, d, 1),
        span("dispatch.wait", 5.3, 5.4, WORKER, d, 1),
        span("dispatch.read", 5.4, 5.5, WORKER, d, 1),
        # a batch of two buckets and one tune of one
        span("tune.static", 0.3, 0.5, COORD, "tune.cold.batch", 1),
        span("tune.profile", 0.5, 0.8, COORD, "tune.cold.batch", 1),
        span("tune.static", 0.8, 0.9, COORD, "tune.cold.batch", 1),
        span("tune.profile", 0.9, 1.2, COORD, "tune.cold.batch", 1),
        span("tune.static", 4.2, 4.5, COORD, "tune.cold", 1),
        span("tune.profile", 4.5, 4.8, COORD, "tune.cold", 1),
        # drains: the first holds the first request's retire
        span("engine.drain", 1.9, 3.5, COORD, why="cold"),
        span("engine.drain", 3.9, 4.0, COORD, why="cold"),
        # a worker's span is not the coordinator's
        span("engine.drain", 6.0, 7.0, WORKER, why="cold"),
    ]


def run_of(spans):
    run = harness.Run(cell=None, seed=0, seconds=10.0, trace=True,
                      t_start=0.0, t_end=10.0, spans=spans)
    run.done = [harness.Done(program="vecadd", rows=8, tenant="t",
                             split=(1, 4), t_pop=0.0, t_retire=1.0,
                             in_bytes=4_000_000, out_bytes=1_000_000,
                             ok=True, traced=False)] * 2
    return run


def test_phase_readers_read_their_definitions():
    run = run_of(stages() + phases())
    value = {m: reader(m).read(run) for m in PHASE_METRICS}
    # issue walls 0.5 + 0.3 less the nested waits 0.2, over 2 dispatches
    assert value["dispatch_issue_ms"] == pytest.approx(300.0)
    assert value["dispatch_wait_ms"] == pytest.approx(200.0)
    assert value["dispatch_read_ms"] == pytest.approx(100.0)
    assert value["dispatch_warmup_ms"] == pytest.approx(100.0)
    # 0.6 s and 0.9 s inside cold tunes, over 3 buckets
    assert value["tune_static_ms"] == pytest.approx(200.0)
    assert value["tune_profile_ms"] == pytest.approx(300.0)
    # the coordinator's drains, 1.6 s and 0.1 s of 10 s
    assert value["drain_share"] == pytest.approx(0.17)


def test_phase_readers_read_nothing_without_the_phases():
    run = run_of(stages())
    assert {m: reader(m).read(run) for m in PHASE_METRICS} \
        == dict.fromkeys(PHASE_METRICS)


def test_phases_sum_to_the_dispatch_wall():
    run = run_of(stages() + phases())
    total = sum(reader(m).read(run) for m in PHASE_METRICS[:4])
    mean = 1e3 * sum(s.duration_s for s in run.spans
                     if s.name == "dispatch") / 2
    # the hand-built requests leave 0.1 s of each dispatch unphased
    assert total == pytest.approx(mean - 100.0)


def test_tune_phases_count_only_inside_cold_tunes():
    stray = [span("tune.static", 8.0, 9.0, COORD),
             span("tune.profile", 8.0, 9.0, COORD, "tune.anchor", 1)]
    run = run_of(stages() + phases() + stray)
    assert reader("tune_static_ms").read(run) == pytest.approx(200.0)
    assert reader("tune_profile_ms").read(run) == pytest.approx(300.0)


def test_warmup_reads_zero_when_nothing_warmed_up():
    run = run_of([s for s in stages() + phases()
                  if s.name != "dispatch.warmup"])
    assert reader("dispatch_warmup_ms").read(run) == 0.0


@pytest.mark.parametrize("name", OLD_METRICS)
def test_older_readers_read_the_same_with_the_phases(name):
    without = reader(name).read(run_of(stages()))
    with_ = reader(name).read(run_of(stages() + phases()))
    assert without is not None and with_ == without


@pytest.fixture(scope="module")
def tiny_traced(tmp_path_factory):
    root = tiny_copy(tmp_path_factory.mktemp("phases"))
    saved = harness.TRACE_AFTER_S, harness.TRACE_FOR_S
    harness.TRACE_AFTER_S, harness.TRACE_FOR_S = 0.2, 0.3
    try:
        cr = harness.CellRun(root, "tiny.mix", 2 ** 31 + 11, 1.5, True,
                             t_process=time.perf_counter(), platform=None)
        cr.setup()
        cr.window()
        line = cr.finish()
    finally:
        harness.TRACE_AFTER_S, harness.TRACE_FOR_S = saved
    return cr.run, line


def test_tiny_traced_run_phases_cover_each_dispatch(tiny_traced):
    run, line = tiny_traced
    assert line["correct"], line["checks"]
    assert set(PHASE_METRICS) <= set(line["metrics"])
    walls = [s.duration_s for s in run.spans if s.name == "dispatch"]
    mean_ms = 1e3 * sum(walls) / len(walls)
    phased = sum(line["metrics"][m]["value"] for m in PHASE_METRICS[:4])
    assert phased == pytest.approx(mean_ms, rel=0.1)
    for m in OLD_METRICS:
        assert line["metrics"][m]["value"] > 0
