"""Pallas TPU flash-attention kernel (causal, GQA) with explicit BlockSpec
VMEM tiling.

TPU adaptation of the paper's streaming idea at the kernel level: the grid
pipeline double-buffers HBM->VMEM DMA of K/V blocks against MXU compute on
the current block — the intra-chip analogue of the paper's host-device
transfer/compute overlap (DESIGN.md §2).

Grid: (batch*kv_head, q_blocks, kv_blocks); kv is the innermost
(fastest-moving) axis so the online-softmax accumulators live in VMEM
scratch across kv steps of one (bh, q_block) tile.  Causal skipping is
predicated with pl.when so fully-masked kv blocks do no MXU work.

Validated against repro.kernels.ref (pure jnp) in the Pallas interpreter
on CPU; compiled with Mosaic on a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, q_block: int, kv_block: int, causal: bool,
                  group: int, seq_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Row r of the tile is query position qi*q_block + r//group.
    q_pos = qi * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block * group, 1), 0) // group
    k_pos = ki * kv_block + jax.lax.broadcasted_iota(
        jnp.int32, (1, kv_block), 1)

    if causal:
        run = (ki * kv_block) <= (qi * q_block + q_block - 1)
    else:
        run = True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)       # (q_block*group, head_dim)
        k = k_ref[0, 0].astype(jnp.float32)       # (kv_block, head_dim)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = k_pos < seq_len
        if causal:
            mask = mask & (q_pos >= k_pos)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, KV, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    q_block: int = 128,
    kv_block: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """GQA flash attention. The G query heads of one KV group are folded
    into the q-block rows so each MXU tile is (q_block*G, head_dim) and K/V
    blocks are fetched once per group rather than once per query head."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    assert H % KV == 0
    group = H // KV
    scale = 1.0 / (hd ** 0.5)

    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    assert Sq % q_block == 0 and Sk % kv_block == 0, (
        "pad sequences to block multiples before calling")
    nq, nk = Sq // q_block, Sk // kv_block

    # (B, Sq, H, hd) -> (B*KV, nq, q_block*G, hd): one grid row per
    # (batch, kv head); the group's query heads ride along in the row dim.
    qg = (q.reshape(B, nq, q_block, KV, group, hd)
          .transpose(0, 3, 1, 2, 4, 5)
          .reshape(B * KV, nq, q_block * group, hd))
    kg = (k.reshape(B, nk, kv_block, KV, hd)
          .transpose(0, 3, 1, 2, 4)
          .reshape(B * KV, nk, kv_block, hd))
    vg = (v.reshape(B, nk, kv_block, KV, hd)
          .transpose(0, 3, 1, 2, 4)
          .reshape(B * KV, nk, kv_block, hd))

    grid = (B * KV, nq, nk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, q_block=q_block, kv_block=kv_block,
        causal=causal, group=group, seq_len=Sk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_block * group, hd),
                         lambda b, qi, ki: (b, qi, 0, 0)),
            pl.BlockSpec((1, 1, kv_block, hd),
                         lambda b, qi, ki: (b, ki, 0, 0)),
            pl.BlockSpec((1, 1, kv_block, hd),
                         lambda b, qi, ki: (b, ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block * group, hd),
                               lambda b, qi, ki: (b, qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (B * KV, nq, q_block * group, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block * group, hd), jnp.float32),   # acc
            pltpu.VMEM((q_block * group, 1), jnp.float32),    # m
            pltpu.VMEM((q_block * group, 1), jnp.float32),    # l
        ],
        interpret=default_interpret() if interpret is None else interpret,
    )(qg, kg, vg)

    # (B*KV, nq, q_block*G, hd) -> (B, Sq, H, hd)
    o = (out.reshape(B, KV, nq, q_block, group, hd)
         .transpose(0, 2, 3, 1, 4, 5)
         .reshape(B, Sq, H, hd))
    return o
