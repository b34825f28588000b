"""Pallas kernels (interpret mode) vs pure-jnp oracles — shape/dtype sweep."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ops import flash_attention, rmsnorm
from repro.kernels.ref import flash_attention_ref, rmsnorm_ref

SHAPES = [
    # B, S, H, KV, hd
    (1, 128, 4, 4, 32),
    (2, 256, 8, 2, 64),
    (1, 256, 6, 3, 128),
    (2, 128, 4, 1, 64),   # MQA
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_attention_pallas(shape, dtype):
    B, S, H, KV, hd = shape
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd)).astype(dtype)
    out = flash_attention(q, k, v, q_block=128, kv_block=128)
    ref = flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert out.dtype == dtype
    assert jnp.allclose(out.astype(jnp.float32), ref.astype(jnp.float32),
                        atol=tol), float(jnp.abs(
                            out.astype(jnp.float32)
                            - ref.astype(jnp.float32)).max())


@pytest.mark.parametrize("blocks", [(64, 64), (128, 64), (64, 128)])
def test_flash_attention_block_sweep(blocks):
    qb, kb = blocks
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 32))
    k = jax.random.normal(ks[1], (1, 256, 2, 32))
    v = jax.random.normal(ks[2], (1, 256, 2, 32))
    out = flash_attention(q, k, v, q_block=qb, kv_block=kb)
    ref = flash_attention_ref(q, k, v)
    assert jnp.allclose(out, ref, atol=2e-5)


@pytest.mark.parametrize("rows,d", [(4, 64), (37, 96), (256, 128), (1, 32)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_rmsnorm_pallas(rows, d, dtype):
    ks = jax.random.split(jax.random.key(2), 2)
    x = jax.random.normal(ks[0], (rows, d)).astype(dtype)
    s = jax.random.normal(ks[1], (d,)).astype(dtype)
    out = rmsnorm(x, s, row_block=64)
    ref = rmsnorm_ref(x, s)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert jnp.allclose(out.astype(jnp.float32), ref.astype(jnp.float32),
                        atol=tol)


def test_rmsnorm_3d():
    x = jax.random.normal(jax.random.key(3), (2, 17, 64))
    s = jnp.ones((64,))
    assert jnp.allclose(rmsnorm(x, s), rmsnorm_ref(x, s), atol=1e-5)


def test_interpret_mode_follows_the_backend(monkeypatch):
    """Mosaic on a TPU backend, the Pallas interpreter anywhere else —
    decided from the backend, with no switch to forget."""
    from repro.kernels import default_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert default_interpret() is False
    for backend in ("cpu", "gpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert default_interpret() is True
