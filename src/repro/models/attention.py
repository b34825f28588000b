"""Attention: RoPE, blocked (flash-style) causal attention in pure jnp,
GQA without KV-head materialization, and distributed flash-decode over a
sequence-sharded KV cache (shard_map over the 'model' mesh axis).

The blocked jnp path is simultaneously the production XLA path for pod-scale
shapes (bounded memory at 32k/500k sequence) and the oracle the Pallas
kernel (repro.kernels.flash_attention) is validated against.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, nheads, head_dim); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    # broadcast over head axis
    angles = angles[..., :, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocked causal flash attention (pure jnp, GQA grouped)
# ---------------------------------------------------------------------------

_NEG_INF = -1e30


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def flash_attention(
    q: jax.Array,      # (B, Sq, H, hd)
    k: jax.Array,      # (B, Sk, KV, hd)
    v: jax.Array,      # (B, Sk, KV, hd)
    *,
    causal: bool = True,
    q_offset: int = 0,          # global position of q[0] (for cached prefill)
    q_block: int = 512,
    kv_block: int = 512,
    logit_scale: Optional[float] = None,
    gqa_grouped: bool = True,
) -> jax.Array:
    """Online-softmax blocked attention. Returns (B, Sq, H, hd).

    gqa_grouped=True computes GQA grouped — q reshaped to (B,Sq,KV,G,hd) so
    K/V are never expanded (best single-device).  gqa_grouped=False expands
    K/V to H heads first: under tensor parallelism the expansion of
    replicated KV to the model-sharded H dim is a local slice (zero
    communication), whereas the grouped reshape of a sharded H into (KV,G)
    makes GSPMD reshard — so the pod path uses the expanded form.
    """
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    assert H % KV == 0, (H, KV)
    if not gqa_grouped and KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        KV = H
    G = H // KV
    scale = logit_scale if logit_scale is not None else 1.0 / (hd ** 0.5)

    qb = min(q_block, Sq)
    kb = min(kv_block, Sk)
    Sq_p, Sk_p = _ceil_to(Sq, qb), _ceil_to(Sk, kb)
    q = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
    nq, nk = Sq_p // qb, Sk_p // kb

    q = q.reshape(B, nq, qb, KV, G, hd)
    k = k.reshape(B, nk, kb, KV, hd)
    v = v.reshape(B, nk, kb, KV, hd)

    q_pos = q_offset + jnp.arange(Sq_p).reshape(nq, qb)
    k_pos = jnp.arange(Sk_p).reshape(nk, kb)
    k_valid = (jnp.arange(Sk_p) < Sk).reshape(nk, kb)

    def q_step(_, qi):
        q_i, qp_i = qi  # (B, qb, KV, G, hd), (qb,)

        def kv_step(carry, ki):
            m, l, acc = carry
            k_j, v_j, kp_j, kv_j = ki
            s = jnp.einsum(
                "bqkgh,bskh->bkgqs", q_i, k_j,
                preferred_element_type=jnp.float32,
            ) * scale
            mask = kv_j[None, :]
            if causal:
                mask = mask & (qp_i[:, None] >= kp_j[None, :])
            s = jnp.where(mask[None, None, None], s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bkgqs,bskh->bkgqh", p, v_j.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, qb), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, qb), jnp.float32)
        a0 = jnp.zeros((B, KV, G, qb, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0), k_pos, k_valid),
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # (B, KV, G, qb, hd) -> (B, qb, KV, G, hd)
        return None, jnp.transpose(out, (0, 3, 1, 2, 4))

    _, o = jax.lax.scan(q_step, None, (jnp.moveaxis(q, 1, 0), q_pos))
    o = jnp.moveaxis(o, 0, 1).reshape(B, Sq_p, H, hd)[:, :Sq]
    return o.astype(v.dtype)


def reference_attention(q, k, v, *, causal=True, q_offset=0,
                        logit_scale=None) -> jax.Array:
    """Naive O(S^2)-memory oracle for tests."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    scale = logit_scale if logit_scale is not None else 1.0 / (hd ** 0.5)
    qg = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qp = q_offset + jnp.arange(Sq)
        mask = qp[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask[None, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bkgqh", p, v.astype(jnp.float32))
    return jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(B, Sq, H, hd).astype(v.dtype)


# ---------------------------------------------------------------------------
# Distributed flash-decode over a sequence-sharded KV cache
# ---------------------------------------------------------------------------
#
# The KV cache (B, S, KV, hd) is sharded S over the 'model' axis (16-way):
# starcoder2's kv=4 heads cannot shard a 16-way axis, but 32k/512k sequences
# can.  Each model-shard holds a contiguous S/16 slab; a decode step
#   1. writes the new k/v into whichever shard owns position `t`,
#   2. computes partial attention (per-shard max / exp-sum / weighted V),
#   3. combines partials with pmax/psum over 'model'  — flash-decode.


def _local_decode_attn(q, k_loc, v_loc, t, shard_base, s_loc, scale):
    """Partial attention of q (B,1,H,hd) against a local cache slab."""
    B, _, H, hd = q.shape
    KV = k_loc.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_loc,
                   preferred_element_type=jnp.float32) * scale
    pos = shard_base + jnp.arange(s_loc)
    mask = pos[None, None, None, :] <= t
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)                                   # (B,KV,G)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p, v_loc.astype(jnp.float32))
    return m, l, o


def decode_attention_sharded(
    q: jax.Array,        # (B, 1, H, hd)
    k_new: jax.Array,    # (B, 1, KV, hd)
    v_new: jax.Array,    # (B, 1, KV, hd)
    k_cache: jax.Array,  # (B, S, KV, hd)  S sharded over 'model'
    v_cache: jax.Array,
    t: jax.Array,        # scalar int32: position being decoded
    *,
    mesh,
    dp_axes: tuple,      # e.g. ('data',) or ('pod','data')
    logit_scale: Optional[float] = None,
):
    """Returns (attn_out (B,1,H,hd), new_k_cache, new_v_cache)."""
    B, _, H, hd = q.shape
    S = k_cache.shape[1]
    scale = logit_scale if logit_scale is not None else 1.0 / (hd ** 0.5)
    n_shards = 1
    for ax in ("model",):
        n_shards *= mesh.shape[ax]
    s_loc = S // n_shards

    dp = tuple(dp_axes) if dp_axes else None
    cache_spec = P(dp, "model", None, None)
    rep_spec = P(dp, None, None, None)

    def body(q, k_new, v_new, k_loc, v_loc, t):
        b_loc = q.shape[0]
        shard = jax.lax.axis_index("model")
        base = shard * s_loc
        # 1. masked cache write: only the owner shard takes the update.
        lp = jnp.clip(t - base, 0, s_loc - 1)
        owns = (t >= base) & (t < base + s_loc)
        k_upd = jax.lax.dynamic_update_slice(k_loc, k_new, (0, lp, 0, 0))
        v_upd = jax.lax.dynamic_update_slice(v_loc, v_new, (0, lp, 0, 0))
        k_loc = jnp.where(owns, k_upd, k_loc)
        v_loc = jnp.where(owns, v_upd, v_loc)
        # 2. partial flash-decode on the local slab.
        m, l, o = _local_decode_attn(q, k_loc, v_loc, t, base, s_loc, scale)
        # 3. combine partials across 'model'.
        m_g = jax.lax.pmax(m, "model")
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, "model")
        o_g = jax.lax.psum(o * corr[..., None], "model")
        out = (o_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(q.dtype)
        out = out.reshape(b_loc, 1, H, hd)
        return out, k_loc, v_loc

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(rep_spec, rep_spec, rep_spec, cache_spec, cache_spec, P()),
        out_specs=(rep_spec, cache_spec, cache_spec),
        check_vma=False,
    )
    return fn(q, k_new, v_new, k_cache, v_cache, t)


def _prod(mesh, axes) -> int:
    n = 1
    for a in axes or ():
        n *= mesh.shape[a]
    return n


def decode_attention_local(q, k_new, v_new, k_cache, v_cache, t, *,
                           logit_scale=None):
    """Single-host decode attention (no mesh) — smoke tests / reference."""
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k_new, (0, t, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v_new, (0, t, 0, 0))
    B, S, KV, hd = k_cache.shape
    H = q.shape[2]
    scale = logit_scale if logit_scale is not None else 1.0 / (hd ** 0.5)
    m, l, o = _local_decode_attn(q, k_cache, v_cache, t, 0, S, scale)
    out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return out.reshape(B, 1, H, hd), k_cache, v_cache
