"""The reduction from a profiler trace to busy time, idle gaps, kernel
time and the readers' metrics: by hand on a made-up trace, and on 40 ms
recorded on a TPU v5e."""
import json
import types

import pytest

import devtrace
import harness
from bench_testkit import BENCH

RECORDED = json.loads((BENCH / "tests/data/tpu_v5e_trace.json").read_text())


def test_reduce_by_hand():
    events = {"ops": [["a", 0, 10], ["b", 5, 10], ["c", 30, 5],
                      ["a", 50, 100]],
              "modules": [["m1", 0, 15], ["m2", 30, 5], ["m3", 50, 100]]}
    red = devtrace.reduce(events, 0, 40)
    assert red["busy_s"] == pytest.approx(20e-9)      # [0,15] + [30,35]
    assert red["window_s"] == pytest.approx(40e-9)
    assert red["gaps_ns"] == [[15, 30], [35, 40]]
    assert red["op_totals_s"] == pytest.approx({"a": 10e-9, "b": 10e-9,
                                                "c": 5e-9})
    assert red["kernel_s"] == pytest.approx(20e-9)
    assert red["kernel_calls"] == 2


def test_combine_two_chips_by_hand():
    """Two chips over one sub-window of 40 ns, each trace on a clock of
    its own: busy, kernel time and op totals add up, the window is the
    sub-window twice, so the idle share is the chips' mean (1 - 20/40
    and 1 - 5/40: 0.6875), and each chip keeps its own gaps."""
    chip0 = {"ops": [["a", 0, 10], ["b", 5, 10], ["c", 30, 5]],
             "modules": [["m1", 0, 15], ["m2", 30, 5]]}
    chip1 = {"ops": [["a", 1010, 5]], "modules": [["m1", 1010, 5]]}
    red = devtrace.combine([devtrace.reduce(chip0, 0, 40),
                            devtrace.reduce(chip1, 1000, 1040)])
    assert red["busy_s"] == pytest.approx(25e-9)      # 20 + 5
    assert red["window_s"] == pytest.approx(80e-9)    # 40 twice
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(
        (0.5 + 0.875) / 2)
    assert red["gaps_ns"] == [[15, 30], [35, 40], [1000, 1010], [1015, 1040]]
    assert red["op_totals_s"] == pytest.approx({"a": 15e-9, "b": 10e-9,
                                                "c": 5e-9})
    assert red["kernel_s"] == pytest.approx(25e-9)    # 15 + 5 + 5
    assert red["kernel_calls"] == 3


@pytest.mark.parametrize("recorded", [False, True])
def test_combine_of_one_chip_is_its_reduction(recorded):
    """A cell on one chip reads what it read before several chips could
    be combined: every field equal, not approximately."""
    if recorded:
        events, (lo, hi) = RECORDED, RECORDED["window_ns"]
    else:
        events, lo, hi = {"ops": [["a", 0, 10], ["b", 5, 10]],
                          "modules": [["m1", 0, 15]]}, 3, 40
    red = devtrace.reduce(events, lo, hi)
    combined = devtrace.combine([red])
    assert list(combined) == list(red)
    for field, value in red.items():
        assert combined[field] == value, field


def test_gaps_named_by_open_spans():
    span = lambda name, t0, t1, depth=0: types.SimpleNamespace(  # noqa: E731
        name=name, t_start=t0, t_end=t1, depth=depth)
    spans = [span("dispatch", 10, 40), span("decide", 12, 18),
             span("retire", 100, 101)]
    named = devtrace.name_gaps([[15, 30], [35, 36], [60, 90]], spans,
                               to_ns=lambda t: t)
    assert [n for n, _ in named] == ["idle", "dispatch", "dispatch"]
    assert [d for _, d in named] == pytest.approx([30e-9, 15e-9, 1e-9])


def test_short_op_names():
    assert devtrace.short_op(
        "%add.1 = f32[16384,256]{1,0:T(8,128)} add(f32[16384,256]{1,0} %a)"
    ) == "%add.1 = f32[16384,256]"


def test_recorded_trace():
    lo, hi = RECORDED["window_ns"]
    red = devtrace.reduce(RECORDED, lo, hi)
    assert red["window_s"] == pytest.approx(0.04)
    assert 0 < red["busy_s"] < red["window_s"]
    # every op runs inside a module, and modules do not overlap
    assert red["busy_s"] <= red["kernel_s"] * (1 + 1e-6)
    assert red["kernel_calls"] == len(RECORDED["modules"])
    gaps = sum(e - s for s, e in red["gaps_ns"]) * 1e-9
    assert gaps + red["busy_s"] == pytest.approx(red["window_s"])
    top = max(red["op_totals_s"], key=red["op_totals_s"].get)
    assert top == "%fusion = f32[2048,16,128]"


def _run(**kw):
    cell = types.SimpleNamespace(bench_dir=BENCH)
    base = dict(cell=cell, done=[], spans=[], device=None, peaks=None,
                t_start=0.0, t_end=10.0, compile_times=[], store={})
    base.update(kw)
    r = types.SimpleNamespace(**base)
    r.counts = lambda p: harness.load_plugin(BENCH, "counts", p,
                                             required=False)
    return r


def _done(program, rows, split, traced=True):
    return harness.Done(program=program, rows=rows, tenant="t", split=split,
                        t_pop=0.0, t_retire=0.1, in_bytes=1, out_bytes=1,
                        ok=True, traced=traced)


def test_idle_share_and_roofline_readers():
    idle = harness.load_plugin(BENCH, "metrics", "device_idle_share")
    roof = harness.load_plugin(BENCH, "metrics", "kernels_roofline")
    peaks = harness.peaks_for(BENCH, "TPU v5 lite")
    dev = {"window_s": 2.0, "busy_s": 0.5, "kernel_s": 0.01}
    # 4 calls of 32768 rows of vecadd: 4 * 3 * 4 * 32768 * 256 bytes
    run = _run(device=dev, peaks=peaks,
               done=[_done("vecadd", 131072, (1, 4)),
                     _done("vecadd", 131072, (1, 4), traced=False)])
    assert idle.read(run) == pytest.approx(0.75)
    least = 4 * 3 * 4 * 32768 * 256 / 819e9
    assert roof.read(run) == pytest.approx(100 * least / 0.01)
    assert idle.read(_run()) is None and roof.read(_run()) is None
    # a program with no count file: nothing to read
    run.done.append(_done("sgemm", 64, (1, 1)))
    assert roof.read(run) is None
