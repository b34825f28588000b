"""Feature extraction from live workloads (paper §3.2)."""
import numpy as np
import pytest

from repro.core.features import RAW_FEATURE_NAMES, extract_features
from repro.core.streams import StreamedRunner
from repro.core.workloads import get_workload


@pytest.fixture(scope="module")
def feats():
    wl = get_workload("mvmult")
    rng = np.random.default_rng(0)
    chunked, shared = wl.make_data(wl.datasets[0], rng)
    runner = StreamedRunner(wl, chunked, shared)
    return extract_features(runner, profile_reps=1).as_dict()


def test_feature_vector_complete(feats):
    assert set(feats) == set(RAW_FEATURE_NAMES)
    assert all(np.isfinite(v) for v in feats.values())


def test_transfer_features(feats):
    wl = get_workload("mvmult")
    rng = np.random.default_rng(0)
    chunked, shared = wl.make_data(wl.datasets[0], rng)
    n = chunked["A"].shape[0]
    assert feats["loop_count"] == n
    assert feats["max_blocks"] == n
    assert feats["dts"] == chunked["A"].nbytes + shared["v"].nbytes
    assert feats["redundant_transfer"] == shared["v"].nbytes
    assert feats["n_xfer_mem"] == 2


def test_static_compiled_features(feats):
    assert feats["flops"] > 0
    assert feats["hlo_ops"] >= 1
    assert 0 <= feats["frac_dot"] <= 1


def test_dynamic_profile_features(feats):
    assert feats["t_single_us"] > 0
    assert feats["t_compute_us"] > 0
    assert feats["t_transfer_us"] > 0


def test_sequential_flag():
    wl = get_workload("binomial")
    rng = np.random.default_rng(0)
    chunked, shared = wl.make_data(wl.datasets[0], rng)
    f = extract_features(StreamedRunner(wl, chunked, shared),
                         profile=False).as_dict()
    assert f["sequential_inner"] == 1.0


@pytest.mark.parametrize("name", ["mvmult", "binomial", "fftx4y3"])
def test_static_and_profiled_features_compose_to_extract_features(
        name, monkeypatch):
    """The scheduler's two halves give extract_features' vector, bit for
    bit (the device timings fixed, as they differ run to run)."""
    from repro.core.features import profiled_features, static_features

    wl = get_workload(name)
    chunked, shared = wl.make_data(wl.datasets[0],
                                   np.random.default_rng(0))
    runner = StreamedRunner(wl, chunked, shared)
    for attr, t in (("measure_transfer", 3e-4), ("measure_compute", 2e-4),
                    ("run_single_stream", 7e-4)):
        monkeypatch.setattr(runner, attr, lambda reps, t=t: t)
    whole = extract_features(runner, profile_reps=1).values
    parts = np.concatenate((static_features(runner),
                            profiled_features(runner, reps=1)))
    assert parts.dtype == whole.dtype and parts.tobytes() == whole.tobytes()
    unprofiled = extract_features(runner, profile=False).values
    assert unprofiled[:18].tobytes() == static_features(runner).tobytes()
    assert not unprofiled[18:].any()
