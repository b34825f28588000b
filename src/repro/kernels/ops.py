"""Jit'd public wrappers around the Pallas kernels.

``interpret=None`` (the default) follows the backend: compiled on a TPU,
the Pallas interpreter elsewhere (:func:`repro.kernels.default_interpret`).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas


@functools.partial(jax.jit, static_argnames=("causal", "q_block", "kv_block",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, q_block: int = 128,
                    kv_block: int = 128, interpret: bool | None = None):
    return flash_attention_pallas(
        q, k, v, causal=causal, q_block=q_block, kv_block=kv_block,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eps", "row_block", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-5, row_block: int = 256,
            interpret: bool | None = None):
    return rmsnorm_pallas(x, scale, eps=eps, row_block=row_block,
                          interpret=interpret)
