"""The heuristic's per-slice overhead, measured on the serving device:
the probe (``streams.probe_slice_overhead``), the model's ``calibrate``
hook and its artifact state, and the scheduler that calibrates before
its first cold tune."""
import collections

import numpy as np
import pytest

from repro.core import streams
from repro.core.features import RAW_FEATURE_NAMES
from repro.core.modeling.heuristic import DEFAULT_OVERHEAD_S
from repro.core.stream_config import SINGLE_STREAM, StreamConfig
from repro.serving import (AdaptiveScheduler, ConcurrentScheduler,
                           OverlapHeuristicModel, TelemetryLog, Tracer,
                           make_trace)
from repro.serving import scheduler as scheduler_mod
from repro.serving.resilience import ResiliencePolicy

BACKENDS = ["host-sync", "host-pipelined", "host-threads"]
CANDIDATES = [StreamConfig(1, 1), StreamConfig(1, 2), StreamConfig(1, 4),
              StreamConfig(2, 4)]


@pytest.fixture
def fresh_memo(monkeypatch):
    """An empty process memo, so that the probe really runs."""
    monkeypatch.setattr(streams, "_SLICE_OVERHEAD", {})


@pytest.fixture
def stub_probe(monkeypatch):
    """Replace the scheduler's probe by a fixed reading; returns the
    list of calls it received."""
    calls = []

    def install(overhead_s: float):
        def probe(backend, device=None):
            calls.append((backend, device))
            return overhead_s
        monkeypatch.setattr(scheduler_mod, "probe_slice_overhead", probe)
        return calls
    return install


def _feats(t_xfer_us: float, t_comp_us: float) -> np.ndarray:
    v = np.ones(len(RAW_FEATURE_NAMES))
    v[RAW_FEATURE_NAMES.index("t_transfer_us")] = t_xfer_us
    v[RAW_FEATURE_NAMES.index("t_compute_us")] = t_comp_us
    v[RAW_FEATURE_NAMES.index("t_single_us")] = t_xfer_us + t_comp_us
    return v


# -- the probe ----------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_probe_is_memoized_per_backend_and_device(backend, fresh_memo,
                                                  monkeypatch):
    runs = collections.Counter()
    run = streams.StreamedRunner.run

    def counted(self, config, **kw):
        runs[config] += 1
        return run(self, config, **kw)

    monkeypatch.setattr(streams.StreamedRunner, "run", counted)
    first = streams.probe_slice_overhead(backend)
    # interleaved sweeps of 1x1 and 1xK, each timed by run()
    split = StreamConfig(1, streams.PROBE_TASKS)
    assert set(runs) == {SINGLE_STREAM, split}
    assert runs[SINGLE_STREAM] == runs[split] >= 3
    before = sum(runs.values())
    import jax
    assert streams.probe_slice_overhead(backend, jax.devices()[0]) == first
    assert sum(runs.values()) == before
    assert first >= 0.0


def test_probe_never_returns_a_negative_overhead(fresh_memo, monkeypatch):
    # a split that reads faster than single-stream (noise) is no overhead
    monkeypatch.setattr(
        streams, "profile_grid_interleaved",
        lambda runner, configs, sweeps: {configs[0]: 2e-3, configs[1]: 1e-3})
    assert streams.probe_slice_overhead("host-sync") == 0.0


def test_probe_reads_the_extra_slices_cost(fresh_memo, monkeypatch):
    k = streams.PROBE_TASKS
    monkeypatch.setattr(
        streams, "profile_grid_interleaved",
        lambda runner, configs, sweeps: {configs[0]: 1e-3,
                                         configs[1]: 1e-3 + (k - 1) * 4e-4})
    assert streams.probe_slice_overhead("host-sync") == pytest.approx(4e-4)


# -- the model's hook and state ----------------------------------------------


def test_uncalibrated_heuristic_scores_with_the_default():
    feats = _feats(1000.0, 1000.0)
    bare = OverlapHeuristicModel()
    pinned = OverlapHeuristicModel(overhead_s=DEFAULT_OVERHEAD_S)
    assert bare.overhead_s == DEFAULT_OVERHEAD_S == 30e-6
    np.testing.assert_array_equal(bare.predict_configs(feats, CANDIDATES),
                                  pinned.predict_configs(feats, CANDIDATES))


def test_calibrate_sets_the_overhead_once():
    m = OverlapHeuristicModel()
    m.calibrate(lambda: 5e-3)
    assert m.overhead_s == 5e-3
    m.calibrate(lambda: pytest.fail("measured twice"))
    assert m.overhead_s == 5e-3


@pytest.mark.parametrize("explicit", [30e-6, 0.0, 2e-3])
def test_explicit_overhead_is_never_overwritten(explicit, stub_probe):
    m = OverlapHeuristicModel(overhead_s=explicit)
    m.calibrate(lambda: pytest.fail("an explicit overhead was measured"))
    assert m.overhead_s == explicit
    # nor by a scheduler's calibration, which then probes nothing
    calls = stub_probe(5e-3)
    sched = AdaptiveScheduler(m, telemetry=TelemetryLog(),
                              keep_outputs=False)
    sched.submit_all(make_trace(["vecadd"], occurrences=1))
    sched.run()
    assert m.overhead_s == explicit
    assert calls == [] and "slice_overhead_us" not in sched.stats


def test_state_round_trips_the_calibrated_overhead(tmp_path):
    m = OverlapHeuristicModel()
    m.calibrate(lambda: 7.5e-4)
    arrays, extras = m.to_state()
    assert extras == {"overhead_s": 7.5e-4}
    back = OverlapHeuristicModel.from_state(arrays, extras)
    assert back.overhead_s == 7.5e-4
    # a loaded value is explicit: a later calibration keeps it
    back.calibrate(lambda: pytest.fail("a restored overhead was measured"))
    # and through an artifact on disk
    loaded = OverlapHeuristicModel.load(m.save(tmp_path / "h"))
    assert loaded.overhead_s == 7.5e-4


def test_state_without_an_overhead_keeps_the_default():
    old = OverlapHeuristicModel.from_state({}, {})
    assert old.overhead_s == DEFAULT_OVERHEAD_S
    old.calibrate(lambda: pytest.fail("an old artifact was measured"))
    assert old.overhead_s == DEFAULT_OVERHEAD_S


# -- the scheduler ------------------------------------------------------------


def _schedulers():
    return [
        pytest.param(lambda **kw: AdaptiveScheduler(**kw), id="serial"),
        pytest.param(lambda **kw: ConcurrentScheduler(window=4, **kw),
                     id="engine"),
    ]


@pytest.mark.parametrize("make", _schedulers())
@pytest.mark.parametrize("overhead_s, single", [(5e-3, True), (0.0, False)])
def test_calibrated_overhead_decides_the_split(make, overhead_s, single,
                                               stub_probe):
    """A bucket whose compute equals its transfer: a 5 ms slice makes
    single-stream win, a free slice makes the most tasks win."""
    stub_probe(overhead_s)
    sched = make(model=OverlapHeuristicModel(), candidates=CANDIDATES,
                 telemetry=TelemetryLog(), keep_outputs=False)

    def extract(pending):
        values = _feats(2000.0, 2000.0)
        sched._feats[pending.key] = values
        sched._t_single[pending.key] = 4e-3
        return values

    sched._extract = extract
    with sched:
        sched.submit_all(make_trace(["vecadd", "dotprod"], occurrences=1))
        results = sched.run()
    assert sched.model.overhead_s == overhead_s
    for r in results:
        if single:
            assert r.config == SINGLE_STREAM
        else:
            assert r.config.tasks > 1


def test_scheduler_records_one_calibrate_span_and_the_counter(stub_probe):
    calls = stub_probe(1.25e-3)
    tr = Tracer()
    sched = ConcurrentScheduler(
        OverlapHeuristicModel(), window=2, tracer=tr,
        telemetry=TelemetryLog(), keep_outputs=False,
        resilience=ResiliencePolicy())
    with sched:
        # two cold waves and a warm one: one calibration
        sched.submit_all(make_trace(["vecadd", "dotprod", "mvmult"],
                                    occurrences=2))
        sched.run()
    (span,) = [s for s in tr.spans if s.name == "tune.calibrate"]
    assert span.attrs == {"overhead_us": pytest.approx(1250.0)}
    assert span.parent is None
    assert sched.stats["slice_overhead_us"] == pytest.approx(1250.0)
    assert len(calls) == 1
    # the resilience ladder's fallback shares the measurement
    assert sched.model.overhead_s == sched._fallback_model.overhead_s \
        == 1.25e-3
    # the probe ran on the device the requests run on
    import jax
    assert calls[0] == ("host-sync", jax.devices()[0])


def test_swapped_in_model_is_calibrated_again(stub_probe):
    stub_probe(2e-3)
    sched = AdaptiveScheduler(OverlapHeuristicModel(),
                              telemetry=TelemetryLog(), keep_outputs=False)
    sched.submit_all(make_trace(["vecadd"], occurrences=1))
    sched.run()
    fresh = OverlapHeuristicModel()
    sched.swap_model(fresh, model_tag="v2")
    sched.submit_all(make_trace(["vecadd"], occurrences=1))
    sched.run()
    assert fresh.overhead_s == 2e-3


def test_scheduler_reads_the_real_probe(fresh_memo):
    """Unstubbed, the scheduler's counter is the process memo's value:
    a second scheduler on the same device reads it without probing."""
    import jax

    scheds = []
    for _ in range(2):
        sched = AdaptiveScheduler(OverlapHeuristicModel(),
                                  backend="host-pipelined",
                                  telemetry=TelemetryLog(),
                                  keep_outputs=False)
        sched.submit_all(make_trace(["vecadd"], occurrences=1))
        sched.run()
        scheds.append(sched)
    memo = streams._SLICE_OVERHEAD[("host-pipelined", jax.devices()[0])]
    assert len(streams._SLICE_OVERHEAD) == 1
    for sched in scheds:
        assert sched.model.overhead_s == memo
        assert sched.stats["slice_overhead_us"] == pytest.approx(memo * 1e6)
