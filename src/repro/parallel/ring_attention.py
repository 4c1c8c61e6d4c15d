"""Ring-style Context Parallelism — the paper's chosen CP substrate.

Key property DHP relies on (§4.1): the ring works for ANY positive
integer degree d, unlike Ulysses-style SP whose all-to-all requires the
degree to divide the head count. On TPU the ring is `jax.lax.ppermute`
over the `cp` mesh axis (neighbour hops on the ICI torus); each hop's
compute is a partial flash-attention with online-softmax accumulators
carried across hops, so communication of hop h+1 overlaps the compute of
hop h (the overlap credit of Eq. 10 — XLA's latency-hiding scheduler
performs the overlap since each hop's ppermute is independent of that
hop's FLOPs).

Mask generality: positions travel WITH the KV shards, so any sequence
layout works. `make_positions(..., striped=True)` gives the Striped
Attention layout (Brandon et al., cited by the paper) that balances the
causal-mask load across ranks; contiguous is the paper-faithful default.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def make_positions(seq_len: int, degree: int, rank: int,
                   striped: bool = False) -> jnp.ndarray:
    """Global token positions owned by `rank` (local order)."""
    per = seq_len // degree
    if striped:
        return jnp.arange(per) * degree + rank
    return rank * per + jnp.arange(per)


def shard_sequence(x: jnp.ndarray, degree: int, rank: int, axis: int = 1,
                   striped: bool = False) -> jnp.ndarray:
    """Slice the tokens a rank owns (host-side data dispatch helper)."""
    per = x.shape[axis] // degree
    if striped:
        idx = jnp.arange(per) * degree + rank
        return jnp.take(x, idx, axis=axis)
    return jax.lax.slice_in_dim(x, rank * per, (rank + 1) * per, axis=axis)


def _partial_update(carry, q, k, v, q_pos, k_pos, mode: str,
                    window: Optional[int], q_seg=None, k_seg=None,
                    q_span=None, k_span=None):
    """One online-softmax accumulation step. q:[B,S,Hkv,G,D] fp32-scaled,
    k/v:[B,T,Hkv,D]. carry = (m, l, acc). `q_seg`/`k_seg` ([B,S]/[B,T]
    int32, -1 = padding) restrict attention to same-segment pairs —
    the packed-varlen mode; `q_span`/`k_span` (-1 = causal) add the
    mixed modality mask (same-id bidirectional blocks attend forward);
    k_seg/k_span arrived with this hop's KV shard."""
    m, l, acc = carry
    s = jnp.einsum("bskgd,btkd->bskgt", q, k.astype(jnp.float32))
    mask = k_pos[:, None, :] <= q_pos[:, :, None]  # [B,S,T]
    if mode == "full":
        mask = jnp.ones_like(mask)
    elif mode == "sliding":
        mask &= k_pos[:, None, :] > (q_pos[:, :, None] - window)
    if q_span is not None and mode != "full":
        mask |= (q_span[:, :, None] >= 0) \
            & (q_span[:, :, None] == k_span[:, None, :])
    if q_seg is not None:
        mask &= (q_seg[:, :, None] == k_seg[:, None, :]) \
            & (q_seg >= 0)[:, :, None]
    bias = jnp.where(mask, 0.0, NEG_INF)
    s = s + bias[:, :, None, None, :]
    m_new = jnp.maximum(m, s.max(axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l = l * corr + p.sum(axis=-1)
    acc = acc * corr[..., None] + jnp.einsum(
        "bskgt,btkd->bskgd", p, v.astype(jnp.float32))
    return m_new, l, acc


def ring_attention(q, k, v, q_pos, *, axis_name: str,
                   mode: str = "causal", window: Optional[int] = None,
                   q_seg=None, q_span=None) -> jax.Array:
    """Executed INSIDE shard_map. q:[B,S_loc,H,D], k/v:[B,S_loc,Hkv,D],
    q_pos:[B,S_loc] global positions of the local shard.

    Any integer ring size is legal — jax.lax.ppermute has no
    power-of-two or head-divisibility constraint (the paper's core
    flexibility argument, §4.1).

    `q_seg` ([B,S_loc] int32, -1 = padding) turns on packed-varlen
    masking: each hop's KV shard travels WITH its position table AND its
    segment table, so attention stays block-diagonal over segments no
    matter which rank currently holds the shard. Positions are
    per-segment (reset at each boundary); the causal comparison is only
    consulted for same-segment pairs, where it is exact.

    `q_span` ([B,S_loc] int32, -1 = causal) is the modality table of
    the local shard: same-id tokens form one bidirectional block
    (vision frame / audio window) that attends FORWARD within itself.
    Like segments and positions, the table rides every ppermute hop, so
    a block sharded across ranks stays bidirectional end to end.
    """
    d = jax.lax.axis_size(axis_name)
    B, S, H, Dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = (q.reshape(B, S, Hkv, G, Dh) / math.sqrt(Dh)).astype(jnp.float32)

    m = jnp.full((B, S, Hkv, G), NEG_INF, jnp.float32)
    l = jnp.zeros((B, S, Hkv, G), jnp.float32)
    acc = jnp.zeros((B, S, Hkv, G, Dh), jnp.float32)
    carry = (m, l, acc)

    k_cur, v_cur, kpos_cur = k, v, q_pos
    kseg_cur = q_seg
    kspan_cur = q_span
    perm = [(i, (i - 1) % d) for i in range(d)]
    for hop in range(d):
        carry = _partial_update(carry, qg, k_cur, v_cur, q_pos, kpos_cur,
                                mode, window, q_seg=q_seg,
                                k_seg=kseg_cur, q_span=q_span,
                                k_span=kspan_cur)
        if hop != d - 1:
            # the hop carries exactly the tables in use: positions
            # always, the segment and modality tables when present
            extras = (() if q_seg is None else (kseg_cur,)) \
                + (() if q_span is None else (kspan_cur,))
            moved = jax.lax.ppermute((k_cur, v_cur, kpos_cur) + extras,
                                     axis_name, perm)
            k_cur, v_cur, kpos_cur = moved[:3]
            if q_seg is not None:
                kseg_cur = moved[3]
            if q_span is not None:
                kspan_cur = moved[-1]
    m, l, acc = carry
    o = acc / jnp.maximum(l[..., None], 1e-30)
    return o.reshape(B, S, H, Dh).astype(q.dtype)


def ring_decode_attention(q1, k_cache, v_cache, local_valid, *,
                          axis_name: str) -> jax.Array:
    """Decode with the KV cache sharded along sequence over `axis_name`
    (CP serving): each rank computes partial (max, sum, acc) over its
    cache shard; a tree psum combines — distributed softmax, one round.

    q1:[B,1,H,D] (replicated across the cp axis), caches [B,T_loc,Hkv,D],
    local_valid:[B] live entries of the local shard.
    """
    B, _, H, Dh = q1.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    qg = (q1.reshape(B, 1, Hkv, G, Dh) / math.sqrt(Dh)).astype(jnp.float32)
    s = jnp.einsum("bskgd,btkd->bskgt", qg,
                   k_cache.astype(jnp.float32))
    live = jnp.arange(T)[None, :] < local_valid[:, None]
    s = jnp.where(live[:, None, None, None, :], s, NEG_INF)
    m_loc = s.max(axis=-1)
    m = jax.lax.pmax(m_loc, axis_name)
    p = jnp.exp(s - m[..., None])
    l = jax.lax.psum(p.sum(axis=-1), axis_name)
    acc = jnp.einsum("bskgt,btkd->bskgd", p,
                     v_cache.astype(jnp.float32))
    acc = jax.lax.psum(acc, axis_name)
    o = acc / jnp.maximum(l[..., None], 1e-30)
    return o.reshape(B, 1, H, Dh).astype(q1.dtype)
