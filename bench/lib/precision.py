"""The arithmetic the plain references run in.

A reference kernel (``bench/reference/<program>.py``) is written once
against a precision object ``P``: it takes its array functions from
``P.xp`` and routes matrix products and the few operations numpy and
jax.numpy spell differently through ``P``'s methods.

``REFERENCE`` is the yardstick: numpy in float64 on the host.
``CONTROL`` is the same kernel one precision step below what the
configurations state (float32 storage, XLA's default dot precision,
which on a TPU is one bf16 pass): elementwise work in bfloat16 and dot
operands rounded to float8 e4m3, on the default JAX device.  A run that
puts the control's outputs in the program's place must come out not
correct (``bench/control.py``).
"""
from __future__ import annotations

import math

import numpy as np


class Float64Host:
    """numpy, float64: the reference every comparison is made against."""

    name = "float64"
    xp = np

    def arr(self, a):
        a = np.asarray(a)
        return a.astype(np.float64) if a.dtype.kind == "f" else a

    def mm(self, a, b):
        return np.matmul(a, b)

    def erf(self, x):
        return _ERF(x)

    def fft2_abs(self, x):
        return np.abs(np.fft.fft2(x))

    def scatter_add(self, size: int, idx, vals):
        return np.bincount(idx, weights=vals, minlength=size)

    def row_hist(self, x, bins: int):
        n = x.shape[0]
        flat = (x + bins * np.arange(n)[:, None]).reshape(-1)
        return np.bincount(flat, minlength=n * bins).reshape(n, bins) \
            .astype(np.float64)

    def out(self, y) -> np.ndarray:
        return np.asarray(y, np.float64)


class LowerOnDevice:
    """jax.numpy on the default device: bfloat16 elementwise, float8
    e4m3 dot operands accumulated in float32 and rounded to bfloat16.

    The dot operands and results are rounded with ``reduce_precision``,
    which XLA keeps inside a jitted program; a pair of ``astype``
    converts there may be folded away (excess precision on a TPU), and
    the control would then compute in the program's own precision."""

    name = "bfloat16/float8"

    def __init__(self):
        import jax
        import jax.numpy as jnp
        self.jax = jax
        self.xp = jnp
        self.low = jnp.bfloat16

    def arr(self, a):
        a = self.xp.asarray(a)
        return a.astype(self.low) if a.dtype.kind == "f" else a

    def _round(self, v, exponent_bits: int, mantissa_bits: int):
        return self.jax.lax.reduce_precision(v, exponent_bits, mantissa_bits)

    def mm(self, a, b):
        jnp = self.xp
        q = lambda v: self._round(v, 4, 3)  # noqa: E731  (float8 e4m3)
        y = jnp.matmul(q(a), q(b), preferred_element_type=jnp.float32)
        return self._round(y, 8, 7).astype(self.low)

    def erf(self, x):
        return self.jax.scipy.special.erf(x)

    def fft2_abs(self, x):
        jnp = self.xp
        return jnp.abs(jnp.fft.fft2(x.astype(jnp.float32))).astype(self.low)

    def scatter_add(self, size: int, idx, vals):
        return self.xp.zeros(size, self.low).at[idx].add(vals)

    def row_hist(self, x, bins: int):
        jnp = self.xp
        n = x.shape[0]
        flat = (x + bins * jnp.arange(n)[:, None]).reshape(-1)
        return jnp.zeros(n * bins, self.low).at[flat].add(1).reshape(n, bins)

    def out(self, y) -> np.ndarray:
        return np.asarray(np.asarray(y.astype(self.xp.float32)), np.float64)


_ERF = np.vectorize(math.erf, otypes=[np.float64])


REFERENCE = Float64Host()
