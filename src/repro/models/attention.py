"""Attention: GQA projections (and MLA, latent K/V) + three mask modes x
three implementations.

Implementations:
  * reference — full score matrix (smoke tests, tiny shapes).
  * chunked   — lax.scan online-softmax over KV blocks (flash-style in
                pure JAX): O(chunk * S) live memory. This is the default
                for dry-runs/large shapes — the compiled HLO stays small
                (one block's compute, scanned).
  * banded    — sliding-window attention, O(S * window) compute: the
                sub-quadratic variant that qualifies dense archs for the
                long_500k decode shape.
  * pallas    — the TPU kernel in kernels/ (selected via cfg.attn_impl).

Modes: "causal" (LLM), "full" (vision/audio encoder — the eta factor of
DHP Eq. 8), "sliding" (RecurrentGemma local attention / long-context
variant).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .layers import apply_rope, dense_init, init_rmsnorm, rms_norm

NEG_INF = -1e30
#: epsilon of MLA's latent RMSNorm (DeepSeek-V2's kv_a_layernorm, built
#: with its modelling code's default; config.json does not give it)
LATENT_EPS = 1e-6


def init_attention(key, d_model: int, n_heads: int, kv_heads: int,
                   head_dim: int, dtype) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, d_model, n_heads * head_dim, dtype),
        "wk": dense_init(kk, d_model, kv_heads * head_dim, dtype),
        "wv": dense_init(kv, d_model, kv_heads * head_dim, dtype),
        "wo": dense_init(ko, n_heads * head_dim, d_model, dtype),
    }


# --------------------------------------------------------------------------
# Cores. q: [B,S,H,D], k/v: [B,T,Hkv,D]. Positions are absolute.
# --------------------------------------------------------------------------
def _pos_mask(qpos, kpos, mode: str, window: Optional[int]):
    """[S,T] positional (causal/full/sliding) boolean mask."""
    if mode == "full":
        return jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    m = kpos[None, :] <= qpos[:, None]
    if mode == "sliding":
        assert window is not None
        m &= kpos[None, :] > (qpos[:, None] - window)
    return m


def _mask_bias(qpos, kpos, mode: str, window: Optional[int]):
    """[S,T] additive bias in fp32."""
    return jnp.where(_pos_mask(qpos, kpos, mode, window), 0.0, NEG_INF)


def _span_mask(span_q, span_k):
    """[B,S,T] bool: (q, k) lie in the SAME bidirectional modality
    block. span ids >= 0 name a block (vision frame / audio window);
    -1 marks causal text and padding. OR-ing this into the positional
    mask lets block members attend FORWARD within their block — the
    mixed mask of DHP Eq. 8."""
    return (span_q[:, :, None] >= 0) \
        & (span_q[:, :, None] == span_k[:, None, :])


def _segment_bias(seg_q, seg_k):
    """[B,S,T] additive bias: NEG_INF across segment boundaries.

    seg < 0 marks tail padding — it never attends nor is attended."""
    same = (seg_q[:, :, None] == seg_k[:, None, :]) \
        & (seg_q >= 0)[:, :, None]
    return jnp.where(same, 0.0, NEG_INF)


def _norm_table(t, B, S, dtype=jnp.int32):
    """[S] or [B,S] id table -> [B,S] in `dtype`."""
    t = jnp.asarray(t, dtype)
    if t.ndim == 1:
        t = jnp.broadcast_to(t[None], (B, S))
    return t


def attn_reference(q, k, v, *, mode: str, window=None, q_offset=0,
                   kv_offset=0, segment_ids=None, span_ids=None):
    """`span_ids` ([B,S] or [S] int32; -1 = causal) adds the mixed mask:
    tokens sharing a nonnegative span id attend bidirectionally within
    the block, embedded in the otherwise causal/sliding stream."""
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, S, Hkv, G, D).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    s = jnp.einsum("bskgd,btkd->bskgt", qg, kf) / math.sqrt(D)
    qpos = q_offset + jnp.arange(S)
    kpos = kv_offset + jnp.arange(T)
    allowed = jnp.broadcast_to(
        _pos_mask(qpos, kpos, mode, window)[None], (B, S, T))
    if span_ids is not None:
        assert T == S, "span-masked attention is self-attention"
        sp = _norm_table(span_ids, B, S)
        allowed = allowed | _span_mask(sp, sp)
    seg = None
    if segment_ids is not None:
        seg = _norm_table(segment_ids, B, S)
        allowed = allowed & (seg[:, :, None] == seg[:, None, :]) \
            & (seg >= 0)[:, :, None]
    s = s + jnp.where(allowed, 0.0, NEG_INF)[:, :, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bskgt,btkd->bskgd", p, v.astype(jnp.float32))
    if seg is not None:
        # tail-padding rows (seg < 0) have no attendable key: emit exact
        # zeros like every other packed implementation, instead of the
        # uniform softmax over an all-NEG_INF row
        o = jnp.where((seg >= 0)[:, :, None, None, None], o, 0.0)
    return o.reshape(B, S, H, v.shape[-1]).astype(q.dtype)


def _kv_blocks(k, v, chunk):
    B, T, Hkv, D = k.shape
    pad = (-T) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_blk = (T + pad) // chunk
    kb = k.reshape(B, n_blk, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blk, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    return kb, vb, n_blk


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _attn_chunked_core(q, k, v, seg_q, seg_k, span_q, span_k, mode,
                       window, q_offset, kv_offset, chunk):
    """Flash attention in pure JAX: online-softmax scan over KV chunks,
    with a custom VJP that RECOMPUTES the probability tiles per chunk in
    the backward pass (flash-attention-2 backward). Live memory is
    O(S*chunk), forward and backward — the property the Pallas kernel
    has on TPU, preserved in the portable path.

    `seg_q`/`seg_k` (None, or float32 [B,S]/[B,T] with -1 = padding)
    switch on packed-varlen masking: attention becomes block-diagonal
    over segments. `span_q`/`span_k` (same convention) switch on the
    mixed modality mask: same-id tokens attend bidirectionally within
    their block. Float dtype so all tables ride through the custom VJP
    as ordinary primals with zero cotangents."""
    o, _ = _attn_chunked_fwd_impl(q, k, v, seg_q, seg_k, span_q, span_k,
                                  mode, window, q_offset, kv_offset,
                                  chunk)
    return o


def attn_chunked(q, k, v, *, mode: str = "causal", window=None,
                 q_offset=0, kv_offset=0, chunk: int = 1024,
                 segment_ids=None, span_ids=None):
    seg_q = seg_k = span_q = span_k = None
    if segment_ids is not None:
        assert k.shape[1] == q.shape[1], \
            "packed segments require self-attention (Sk == Sq)"
        seg_q = seg_k = _norm_table(segment_ids, q.shape[0], q.shape[1],
                                    jnp.float32)
    if span_ids is not None:
        assert k.shape[1] == q.shape[1], \
            "modality spans require self-attention (Sk == Sq)"
        span_q = span_k = _norm_table(span_ids, q.shape[0], q.shape[1],
                                      jnp.float32)
    return _attn_chunked_core(q, k, v, seg_q, seg_k, span_q, span_k,
                              mode, window, q_offset, kv_offset, chunk)


def _seg_chunks(seg_k, chunk, n_blk):
    """[B,T] float seg table -> [n_blk, B, chunk] scan slices."""
    B, T = seg_k.shape
    pad = n_blk * chunk - T
    segp = jnp.pad(seg_k, ((0, 0), (0, pad)), constant_values=-1.0)
    return segp.reshape(B, n_blk, chunk).transpose(1, 0, 2)


def _chunk_bias_seg(qpos, i, chunk, T, mode, window, kv_offset,
                    seg_q, seg_kc, span_q=None, span_kc=None):
    """[B or 1, S, chunk] bias: positional mask, OR'd with the
    bidirectional-block mask, AND'd with the segment mask."""
    kpos = kv_offset + i * chunk + jnp.arange(chunk)
    allowed = (_pos_mask(qpos, kpos, mode, window)
               & (kpos[None, :] < kv_offset + T))[None]
    if span_q is not None:
        # span tables pad with -1, so padded KV slots never match
        allowed = allowed | _span_mask(span_q, span_kc)
    if seg_q is not None:
        allowed = allowed & (seg_q[:, :, None] == seg_kc[:, None, :]) \
            & (seg_q >= 0)[:, :, None]
    return jnp.where(allowed, 0.0, NEG_INF)


def _table_chunks(tab, chunk, n_blk):
    return (_seg_chunks(tab, chunk, n_blk) if tab is not None
            else jnp.zeros((n_blk, 1, 1)))


def _attn_chunked_fwd_impl(q, k, v, seg_q, seg_k, span_q, span_k, mode,
                           window, q_offset, kv_offset, chunk):
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    chunk = min(chunk, T)
    kb, vb, n_blk = _kv_blocks(k, v, chunk)
    segb = _table_chunks(seg_k, chunk, n_blk)
    spanb = _table_chunks(span_k, chunk, n_blk)
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, S, Hkv, G, D).astype(jnp.float32)
    qpos = q_offset + jnp.arange(S)

    m0 = jnp.full((B, S, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, S, Hkv, G, v.shape[-1]), jnp.float32)

    def body(carry, blk):
        m, l, acc = carry
        kc, vc, i, segc, spanc = blk
        s = jnp.einsum("bskgd,btkd->bskgt", qg,
                       kc.astype(jnp.float32)) * scale
        s = s + _chunk_bias_seg(
            qpos, i, chunk, T, mode, window, kv_offset, seg_q, segc,
            span_q, spanc)[:, :, None, None, :]
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bskgt,btkd->bskgd", p, vc.astype(jnp.float32))
        return (m_new, l, acc), None

    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kb, vb, jnp.arange(n_blk), segb, spanb))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))           # [B,S,Hkv,G]
    o = acc / jnp.maximum(l[..., None], 1e-30)
    out = o.reshape(B, S, H, v.shape[-1]).astype(q.dtype)
    return out, lse


def _attn_chunked_fwd(q, k, v, seg_q, seg_k, span_q, span_k, mode,
                      window, q_offset, kv_offset, chunk):
    out, lse = _attn_chunked_fwd_impl(q, k, v, seg_q, seg_k, span_q,
                                      span_k, mode, window, q_offset,
                                      kv_offset, chunk)
    return out, (q, k, v, seg_q, seg_k, span_q, span_k, out, lse)


def _attn_chunked_bwd(mode, window, q_offset, kv_offset, chunk, res, g):
    q, k, v, seg_q, seg_k, span_q, span_k, out, lse = res
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    chunk = min(chunk, T)
    kb, vb, n_blk = _kv_blocks(k, v, chunk)
    segb = _table_chunks(seg_k, chunk, n_blk)
    spanb = _table_chunks(span_k, chunk, n_blk)
    scale = 1.0 / math.sqrt(D)
    Dv = v.shape[-1]
    qg = q.reshape(B, S, Hkv, G, D).astype(jnp.float32)
    gg = g.reshape(B, S, Hkv, G, Dv).astype(jnp.float32)
    og = out.reshape(B, S, Hkv, G, Dv).astype(jnp.float32)
    delta = jnp.sum(gg * og, axis=-1)                   # [B,S,Hkv,G]
    qpos = q_offset + jnp.arange(S)

    def body(dq, blk):
        kc, vc, i, segc, spanc = blk
        s = jnp.einsum("bskgd,btkd->bskgt", qg,
                       kc.astype(jnp.float32)) * scale
        s = s + _chunk_bias_seg(
            qpos, i, chunk, T, mode, window, kv_offset, seg_q, segc,
            span_q, spanc)[:, :, None, None, :]
        p = jnp.exp(s - lse[..., None])                 # recomputed tile
        dv = jnp.einsum("bskgt,bskgd->btkd", p, gg)
        dp = jnp.einsum("bskgd,btkd->bskgt", gg, vc.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bskgt,btkd->bskgd", ds,
                             kc.astype(jnp.float32))
        dk = jnp.einsum("bskgt,bskgd->btkd", ds, qg)
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, S, Hkv, G, D), jnp.float32)
    dq, (dkb, dvb) = jax.lax.scan(
        body, dq0, (kb, vb, jnp.arange(n_blk), segb, spanb))
    dk = dkb.transpose(1, 0, 2, 3, 4).reshape(B, n_blk * chunk, Hkv, D)
    dv = dvb.transpose(1, 0, 2, 3, 4).reshape(B, n_blk * chunk, Hkv, Dv)
    zero_like = lambda t: None if t is None else jnp.zeros_like(t)  # noqa: E731
    return (dq.reshape(B, S, H, D).astype(q.dtype),
            dk[:, :T].astype(k.dtype), dv[:, :T].astype(v.dtype),
            zero_like(seg_q), zero_like(seg_k),
            zero_like(span_q), zero_like(span_k))


_attn_chunked_core.defvjp(_attn_chunked_fwd, _attn_chunked_bwd)


def attn_banded(q, k, v, *, window: int, q_offset=0, chunk: int = 512):
    """Sliding-window attention with O(S*window) compute.

    K/V are front-padded by w_pad = ceil(window/chunk)*chunk so every q
    block attends a static-size [w_pad + chunk] kv slice starting at its
    own block offset — compute is truly banded, not masked-out.
    """
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    assert S == T, "banded core is for self-attention (prefill/train)"
    G = H // Hkv
    chunk = min(chunk, S)
    pad_s = (-S) % chunk
    if pad_s:
        q = jnp.pad(q, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
    Sp = S + pad_s
    n_blk = Sp // chunk
    w_pad = -(-window // chunk) * chunk
    kp = jnp.pad(k, ((0, 0), (w_pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (w_pad, 0), (0, 0), (0, 0)))

    qb = q.reshape(B, n_blk, chunk, H, D).transpose(1, 0, 2, 3, 4)

    def block(i, qc):
        # kv slice covering positions [i*chunk - w_pad, i*chunk + chunk)
        kc = jax.lax.dynamic_slice_in_dim(kp, i * chunk, w_pad + chunk, 1)
        vc = jax.lax.dynamic_slice_in_dim(vp, i * chunk, w_pad + chunk, 1)
        qg = (qc.reshape(B, chunk, Hkv, G, D)
              / math.sqrt(D)).astype(jnp.float32)
        s = jnp.einsum("bskgd,btkd->bskgt", qg, kc.astype(jnp.float32))
        qpos = q_offset + i * chunk + jnp.arange(chunk)
        kpos = q_offset + i * chunk - w_pad + jnp.arange(w_pad + chunk)
        bias = _mask_bias(qpos, kpos, "sliding", window)
        # mask front padding & tail padding
        valid = (kpos >= q_offset) & (kpos < q_offset + S)
        bias = jnp.where(valid[None, :], bias, NEG_INF)
        s = s + bias[None, :, None, None, :]
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bskgt,btkd->bskgd", p, vc.astype(jnp.float32))
        return o.reshape(B, chunk, H, D)

    def body(_, blk):
        i, qc = blk
        return None, block(i, qc)

    _, ob = jax.lax.scan(body, None, (jnp.arange(n_blk), qb))
    o = ob.transpose(1, 0, 2, 3, 4).reshape(B, Sp, H, D)
    return o[:, :S].astype(q.dtype)


def attn_decode(q1, k_cache, v_cache, valid_len, *, mode: str = "causal",
                window: Optional[int] = None):
    """One-token decode: q1 [B,1,H,D] vs cache [B,T,Hkv,D].

    `valid_len` [B] — number of live cache entries. For sliding-window
    caches the ring buffer already holds only the window, so every live
    entry is attendable.
    """
    B, _, H, D = q1.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    qg = (q1.reshape(B, 1, Hkv, G, D) / math.sqrt(D)).astype(jnp.float32)
    s = jnp.einsum("bskgd,btkd->bskgt", qg, k_cache.astype(jnp.float32))
    live = jnp.arange(T)[None, :] < valid_len[:, None]        # [B,T]
    s = jnp.where(live[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bskgt,btkd->bskgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, D).astype(q1.dtype)


def attn_prefill_chunk(q, k_cache, v_cache, start_pos,
                       chunk_span_ids=None, cache_span_ids=None):
    """Chunked-prefill attention: q [B,C,H,D] at absolute positions
    start_pos..start_pos+C-1 vs a KV cache [B,T,Hkv,D] whose rows
    [0, start_pos+C) are live (the chunk's own K/V must already be
    written at its positions). Causal over absolute position: query i
    attends cache rows j <= start_pos + i.

    Rows past the live region are never attended (j > start_pos + i for
    every query in the chunk), so garbage beyond the written prefix —
    e.g. padding rows of a bucketed final chunk — cannot leak in.

    `chunk_span_ids` [B,C] / `cache_span_ids` [B,T] (int32, -1 = causal)
    switch on the mixed modality mask: a query inside a bidirectional
    block (vision frame / audio window) additionally attends FORWARD to
    same-block cache rows, restricted to the written region
    [0, start_pos+C) — exact as long as the serving scheduler never
    splits a bidirectional span across chunks (it snaps chunk
    boundaries to span ends).
    """
    B, C, H, D = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    qg = (q.reshape(B, C, Hkv, G, D) / math.sqrt(D)).astype(jnp.float32)
    s = jnp.einsum("bckgd,btkd->bckgt", qg, k_cache.astype(jnp.float32))
    qpos = start_pos + jnp.arange(C)                           # [C]
    live = jnp.arange(T)[None, :] <= qpos[:, None]             # [C,T]
    allowed = jnp.broadcast_to(live[None], (B, C, T))
    if chunk_span_ids is not None:
        bidir = _span_mask(jnp.asarray(chunk_span_ids, jnp.int32),
                           jnp.asarray(cache_span_ids, jnp.int32))
        written = jnp.arange(T)[None, None, :] < start_pos + C
        allowed = allowed | (bidir & written)
    s = jnp.where(allowed[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bckgt,btkd->bckgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, C, H, D).astype(q.dtype)


# --------------------------------------------------------------------------
# Full attention block (projections + rope + core dispatch)
# --------------------------------------------------------------------------
def attention(params: dict, x: jax.Array, *, n_heads: int, kv_heads: int,
              head_dim: int, rope_theta: float, positions=None,
              mode: str = "causal", window: Optional[int] = None,
              impl: str = "chunked", rope_frac: float = 1.0,
              cross_kv: Optional[tuple] = None,
              cp_axis: Optional[str] = None,
              attn_chunk: int = 1024,
              segment_ids=None,
              span_ids=None,
              return_kv: bool = False):
    """`segment_ids` ([B,S] int32, -1 = padding) selects the packed
    varlen path: x is a packed buffer of concatenated sequences and
    attention is block-diagonal over segments (causal/full/sliding
    *within* each). Pass per-segment-reset `positions` so RoPE matches.

    `span_ids` ([B,S] int32, -1 = causal) switches on the mixed
    modality mask: tokens sharing a nonnegative id form a bidirectional
    block (vision frame / audio window) embedded in the causal stream —
    composable with `segment_ids` (blocks never cross segments by
    construction) and with any impl, including the ring-CP path where
    the table rides the ppermute hops.
    """
    B, S, _ = x.shape
    q = (x @ params["wq"]).reshape(B, S, n_heads, head_dim)
    if cross_kv is None:
        k = (x @ params["wk"]).reshape(B, S, kv_heads, head_dim)
        v = (x @ params["wv"]).reshape(B, S, kv_heads, head_dim)
        if positions is None:
            positions = jnp.arange(S)[None, :].repeat(B, 0)
        q = apply_rope(q, positions, rope_theta, rope_frac)
        k = apply_rope(k, positions, rope_theta, rope_frac)
    else:
        k, v = cross_kv
        mode = "full"
    o = _attend(q, k, v, positions, mode=mode, window=window, impl=impl,
                cp_axis=None if cross_kv is not None else cp_axis,
                attn_chunk=attn_chunk, segment_ids=segment_ids,
                span_ids=span_ids)
    out = o.reshape(B, S, n_heads * head_dim) @ params["wo"]
    if return_kv:
        return out, (k, v)
    return out


def _attend(q, k, v, positions, *, mode, window, impl, cp_axis,
            attn_chunk, segment_ids, span_ids):
    """The attention core `impl` (or the ring over `cp_axis`) on
    projected q [B,S,H,D], k [B,T,Hkv,D], v [B,T,Hkv,Dv]: o [B,S,H,Dv]."""
    B, S = q.shape[:2]
    if cp_axis is not None:
        # Ring-style context parallelism (inside shard_map): the
        # sequence axis of x/positions/segment_ids is sharded over
        # `cp_axis`; the segment table travels with each KV hop.
        from ..parallel.ring_attention import ring_attention
        return ring_attention(q, k, v, positions, axis_name=cp_axis,
                              mode=mode, window=window,
                              q_seg=segment_ids, q_span=span_ids)
    if impl == "pallas":
        if q.shape[-1] != v.shape[-1]:
            raise NotImplementedError(
                "the Pallas flash kernels take one head dim for q, k and v")
        if segment_ids is not None or span_ids is not None:
            from ..kernels.ops import flash_attention_packed
            seg = (segment_ids if segment_ids is not None
                   else jnp.zeros((B, S), jnp.int32))
            return flash_attention_packed(q, k, v, seg, span_ids=span_ids,
                                          mode=mode, window=window)
        from ..kernels.ops import flash_attention
        return flash_attention(q, k, v, mode=mode, window=window)
    if impl == "reference":
        return attn_reference(q, k, v, mode=mode, window=window,
                              segment_ids=segment_ids, span_ids=span_ids)
    if (mode == "sliding" and impl == "chunked"
            and segment_ids is None and span_ids is None):
        return attn_banded(q, k, v, window=window,
                           chunk=min(attn_chunk, 512))
    return attn_chunked(q, k, v, mode=mode, window=window,
                        chunk=attn_chunk, segment_ids=segment_ids,
                        span_ids=span_ids)


# --------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2 MLA, full q projection)
# --------------------------------------------------------------------------
def init_mla(key, d_model: int, n_heads: int, mla, dtype) -> dict:
    """wq [D, H*(dn+dr)]; wkv_a [D, r+dr] (the latent and the shared rope
    key); kv_norm, the latent's RMSNorm; wkv_b [r, H*(dn+dv)] (each
    head's k_nope then v); wo [H*dv, D]."""
    kq, ka, kb, ko = jax.random.split(key, 4)
    r, dn, dv = mla.kv_lora_rank, mla.qk_nope_head_dim, mla.v_head_dim
    return {
        "wq": dense_init(kq, d_model, n_heads * mla.qk_head_dim, dtype),
        "wkv_a": dense_init(ka, d_model, r + mla.qk_rope_head_dim, dtype),
        "kv_norm": init_rmsnorm(r, dtype),
        "wkv_b": dense_init(kb, r, n_heads * (dn + dv), dtype),
        "wo": dense_init(ko, n_heads * dv, d_model, dtype),
    }


def mla_attention(params: dict, x: jax.Array, *, n_heads: int, mla,
                  rope_theta: float, positions=None, mode: str = "causal",
                  window: Optional[int] = None, impl: str = "chunked",
                  cp_axis: Optional[str] = None, attn_chunk: int = 1024,
                  segment_ids=None, span_ids=None):
    """q = x·wq, per head [nope ‖ rope], rope on the rope dims;
    [c, k_rope] = x·wkv_a; c = RMSNorm(c); one k_rope, roped, for all
    heads; [k_nope ‖ v] = c·wkv_b per head; k = [k_nope ‖ k_rope];
    scores scaled by 1/sqrt(dn + dr); o·wo. The up-projected K and V go
    to the core (and ride the ring's hops). Masks as in `attention`."""
    B, S, _ = x.shape
    r, dn, dr = mla.kv_lora_rank, mla.qk_nope_head_dim, mla.qk_rope_head_dim
    dv = mla.v_head_dim
    if positions is None:
        positions = jnp.arange(S)[None, :].repeat(B, 0)
    q = (x @ params["wq"]).reshape(B, S, n_heads, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], apply_rope(q[..., dn:], positions, rope_theta)], -1)
    kv_a = x @ params["wkv_a"]
    c = rms_norm(params["kv_norm"], kv_a[..., :r], LATENT_EPS)
    k_rope = apply_rope(kv_a[..., r:].reshape(B, S, 1, dr), positions,
                        rope_theta)
    kv = (c @ params["wkv_b"]).reshape(B, S, n_heads, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (B, S, n_heads, dr))], -1)
    o = _attend(q, k, kv[..., dn:], positions, mode=mode, window=window,
                impl=impl, cp_axis=cp_axis, attn_chunk=attn_chunk,
                segment_ids=segment_ids, span_ids=span_ids)
    return o.reshape(B, S, n_heads * dv) @ params["wo"]


def project_qkv_decode(params, x1, *, n_heads, kv_heads, head_dim,
                       rope_theta, position, rope_frac: float = 1.0):
    """Projections for one decode token; position [B] absolute."""
    B = x1.shape[0]
    q = (x1 @ params["wq"]).reshape(B, 1, n_heads, head_dim)
    k = (x1 @ params["wk"]).reshape(B, 1, kv_heads, head_dim)
    v = (x1 @ params["wv"]).reshape(B, 1, kv_heads, head_dim)
    pos = position[:, None]
    q = apply_rope(q, pos, rope_theta, rope_frac)
    k = apply_rope(k, pos, rope_theta, rope_frac)
    return q, k, v
