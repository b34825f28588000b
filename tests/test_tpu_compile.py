"""Compiles for a described (not attached) TPU v5e chip: the main path's
kernels at their largest registered dataset, as the streamed executor
jits them (plain and donating), and the Pallas kernels lowered to Mosaic
at real widths.  Nothing runs; what the chip's compiler would refuse
fails here.

The topology is described inside a module fixture, never while a module
is imported: only one process may load libtpu, and xdist workers import
every test file.  The persistent compilation cache stays off around
these compiles (an entry for a described chip cannot be read back).
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core.backends.base import memoized_jit
from repro.core.workloads import get_workload
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas

#: v5e HBM per chip (Google Cloud documentation, "TPU v5e")
HBM_BYTES = 16 * 2**30

PROGRAMS = ("vecadd", "mvmult", "scalarprod", "binomial", "blackscholes")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
            try:
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 — any failure: "can't"
                pytest.skip(f"no v5e:2x2 topology can be described here: "
                            f"{e}")
            yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(arrays: dict, sharding, rows=None) -> dict:
    return {k: jax.ShapeDtypeStruct(
        (a.shape if rows is None else (rows,) + a.shape[1:]), a.dtype,
        sharding=sharding) for k, a in arrays.items()}


def _fits(compiled) -> bool:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("donate", [False, True], ids=["plain", "donating"])
@pytest.mark.parametrize("name", PROGRAMS)
def test_workload_kernel_compiles_for_v5e(one_chip, name, donate):
    wl = get_workload(name)
    # shapes of the largest dataset, from a tiny instance's dtypes/ranks
    chunked, shared = wl.make_data(8, np.random.default_rng(0))
    with warnings.catch_warnings():
        # a kernel whose output cannot alias its inputs leaves the
        # donation unused; that is the executor's normal case, not an error
        warnings.filterwarnings("ignore", message=".*donated buffers.*")
        compiled = memoized_jit(wl.kernel, donate=donate).lower(
            _shapes(chunked, one_chip, rows=wl.datasets[-1]),
            _shapes(shared, one_chip)).compile()
    assert _fits(compiled)
    assert compiled.cost_analysis()["flops"] > 0


def test_rmsnorm_pallas_compiles_to_mosaic(one_chip):
    x = jax.ShapeDtypeStruct((8192, 4096), jnp.bfloat16, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((4096,), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda x, s: rmsnorm_pallas(x, s, interpret=False)
                       ).lower(x, scale).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits(compiled)


def test_flash_attention_pallas_compiles_to_mosaic(one_chip):
    q = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 128), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention_pallas(
        q, k, v, interpret=False)).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits(compiled)
