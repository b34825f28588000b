"""Pallas TPU fused RMSNorm kernel.

Row-blocked: each grid step normalizes a (row_block, d) tile held in VMEM —
one HBM read + one HBM write per element (the unfused XLA graph reads x
twice: once for the variance reduce, once for the scale multiply).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pallas(x: jax.Array, scale: jax.Array, *, eps: float = 1e-5,
                   row_block: int = 256,
                   interpret: bool | None = None) -> jax.Array:
    """x (..., d), scale (d,).  ``interpret=None`` follows the backend."""
    orig_shape = x.shape
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    rb = min(row_block, n)
    pad = (-n) % rb
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    grid = (xf.shape[0] // rb,)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((rb, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=default_interpret() if interpret is None else interpret,
    )(xf, scale)
    if pad:
        out = out[:n]
    return out.reshape(orig_shape)
