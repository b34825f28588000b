"""Workload suite: all 39 programs run, and chunk-split invariance holds
for the row-independent ones (the property streaming relies on)."""
import jax
import numpy as np
import pytest

from repro.core.workloads import _REGISTRY, get_workload, list_workloads


def test_suite_has_39_programs():
    assert len(list_workloads()) == 39
    suites = {w.suite for w in _REGISTRY.values()}
    assert suites == {"nvidia", "amd", "parboil", "polybench"}


def test_each_program_has_enough_datasets():
    for name in list_workloads():
        assert len(get_workload(name).datasets) >= 8, name


@pytest.mark.parametrize("name", list_workloads())
def test_kernel_runs_and_finite(name):
    wl = get_workload(name)
    rng = np.random.default_rng(0)
    chunked, shared = wl.make_data(wl.datasets[0], rng)
    out = jax.jit(wl.kernel)(chunked, shared)
    for leaf in jax.tree.leaves(out):
        assert np.isfinite(np.asarray(leaf)).all(), name


@pytest.mark.parametrize("name", [n for n in list_workloads()
                                  if get_workload(n).combine == "concat"])
def test_chunk_invariance(name):
    """kernel(rows) == concat(kernel(row chunks)) for row-independent
    programs — the correctness contract of the streamed executor."""
    wl = get_workload(name)
    rng = np.random.default_rng(1)
    chunked, shared = wl.make_data(wl.datasets[0], rng)
    full = np.asarray(jax.jit(wl.kernel)(chunked, shared))
    n = next(iter(chunked.values())).shape[0]
    half = n // 2
    a = {k: v[:half] for k, v in chunked.items()}
    b = {k: v[half:] for k, v in chunked.items()}
    parts = np.concatenate([
        np.asarray(jax.jit(wl.kernel)(a, shared)),
        np.asarray(jax.jit(wl.kernel)(b, shared))], axis=0)
    # gemm reduction order differs across chunk shapes in XLA; 3mm chains
    # two 256-dim contractions so values reach ~1e3-1e4
    np.testing.assert_allclose(parts, full, rtol=1e-3, atol=0.1)


@pytest.mark.parametrize("name", [n for n in list_workloads()
                                  if get_workload(n).combine == "sum"])
def test_sum_partials(name):
    wl = get_workload(name)
    rng = np.random.default_rng(2)
    chunked, shared = wl.make_data(wl.datasets[0], rng)
    full = np.asarray(jax.jit(wl.kernel)(chunked, shared))
    n = next(iter(chunked.values())).shape[0]
    half = n // 2
    a = {k: v[:half] for k, v in chunked.items()}
    b = {k: v[half:] for k, v in chunked.items()}
    parts = (np.asarray(jax.jit(wl.kernel)(a, shared))
             + np.asarray(jax.jit(wl.kernel)(b, shared)))
    np.testing.assert_allclose(parts, full, rtol=1e-3)


def test_kernels_are_named_after_their_programs():
    """A compiled module (and so a device trace) names its program; the
    three FFT variants share one kernel body but not its name."""
    for name in list_workloads():
        wl = get_workload(name)
        assert wl.kernel.__name__ == wl.kernel.__qualname__ == name
    ffts = [get_workload(n).kernel for n in ("fftx1y1", "fftx2y2", "fftx4y3")]
    assert len({id(k) for k in ffts}) == 3
    assert len({k.__code__ for k in ffts}) == 1
    wl = get_workload("jacobi-2d")
    chunked, shared = wl.make_data(wl.datasets[0], np.random.default_rng(0))
    hlo = jax.jit(wl.kernel).lower(chunked, shared).as_text()
    assert "jit_jacobi-2d" in hlo.splitlines()[0]
