"""Pallas TPU flash-attention kernel.

The compute hot-spot DHP's cost model centres on (the a1*(1+eta)|s|^2
term of Eq. 8). TPU-native design, not a CUDA port:

  * grid = (batch*heads, num_q_blocks, num_kv_blocks); the LAST axis is
    sequential on TPU, so the online-softmax running state (m, l, acc)
    lives in VMEM scratch carried across kv iterations — the TPU analogue
    of a CUDA persistent-CTA loop.
  * BlockSpecs tile Q/K/V into (BLOCK_Q x HEAD_DIM) / (BLOCK_K x
    HEAD_DIM) VMEM windows; 128-multiples align with MXU systolic tiles
    and the (8,128) VREG lanes.
  * mask modes: causal / full / sliding(window) + a kv_offset so the
    SAME kernel computes each hop of ring attention (KV blocks arriving
    from a ppermute neighbour carry their global offset).
  * causal/sliding hops skip fully-masked KV blocks via pl.when —
    compute truly drops, unlike a masked dense matmul.
  * packed varlen mode (`flash_attention_packed_flat`): a whole atomic
    group concatenated into ONE token buffer with a segment-id table;
    attention is block-diagonal over segments and cross-segment /
    padding / future-causal KV tiles are skipped via pl.when. This is
    what collapses the executor's executable key space (see
    core/executor.py) — group shape no longer depends on how many
    sequences were packed, only on the padded packed bucket.
  * mixed modality mask: a span-id table rides next to the segment
    table; same-id tokens (one bidirectional vision frame / audio
    window) attend each other regardless of order inside their segment
    — the mask DHP's Eq. 8 eta factor costs (span ids -1 = causal).

Validated against ref.flash_attention_ref / ref.flash_attention_packed_ref
in interpret mode on CPU; compiled by Mosaic on TPU (`interpret_mode`).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            mode: str, window: Optional[int], sm_scale: float,
            block_q: int, block_k: int, kv_offset: int, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # absolute positions of this tile
    q_start = qi * block_q
    k_start = kv_offset + ki * block_k

    # block-level skip: entire KV tile masked out?
    if mode == "full":
        full_skip = False
    elif mode == "causal":
        # kv block strictly after the last q row -> skip
        full_skip = k_start > q_start + block_q - 1
    else:  # sliding
        full_skip = jnp.logical_or(
            k_start > q_start + block_q - 1,
            k_start + block_k - 1 <= q_start - window)

    @pl.when(jnp.logical_not(full_skip) if mode != "full" else True)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = kpos < kv_offset + kv_len           # tail padding
        if mode != "full":
            mask &= kpos <= qpos
            if mode == "sliding":
                mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def _packed_kernel(q_ref, k_ref, v_ref, segq_ref, segk_ref, *refs,
                   mode: str, window: Optional[int], sm_scale: float,
                   block_q: int, block_k: int, kv_offset: int,
                   has_spans: bool):
    """Segment-aware (packed varlen) flash attention tile with the
    mixed modality mask.

    All sequences of a group live concatenated in ONE token buffer;
    attention is block-diagonal across segment boundaries. Inside a
    segment, packed indices are monotone in position, so the causal /
    sliding structure is expressed directly in packed coordinates; with
    `has_spans` (a STATIC flag — span-free callers get the exact
    pre-span kernel, no dummy tables or dead mask work) a span table
    (-1 = causal text/padding) additionally lets same-id tokens — one
    bidirectional vision frame / audio window — attend FORWARD within
    their block, the mixed mask of DHP Eq. 8. A KV tile with no
    attendable (q, k) pair is skipped via pl.when — the MXU work truly
    drops, it is not a masked dense matmul.
    """
    if has_spans:
        spanq_ref, spank_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = kv_offset + ki * block_k
    seg_q = segq_ref[0]                                  # [bq, 1] int32
    seg_k = segk_ref[0]                                  # [1, bk] int32
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                              (block_q, block_k), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                              (block_q, block_k), 1)
    # same segment; padding (seg < 0) never attends or is attended
    valid = (seg_q == seg_k) & (seg_q >= 0)
    if mode != "full":
        ok = kpos <= qpos
        if mode == "sliding":
            ok &= kpos > qpos - window
        if has_spans:
            span_q = spanq_ref[0]                        # [bq, 1] int32
            span_k = spank_ref[0]                        # [1, bk] int32
            ok |= (span_q >= 0) & (span_q == span_k)
        valid &= ok
    # O(bq*bk) mask vs O(bq*bk*D) matmuls: deciding the skip costs 1/D
    # of the tile; fully-masked tiles (cross-segment, future-causal,
    # out-of-window, tail padding) skip both MXU passes.
    live = jnp.any(valid)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        # rows of this tile with no valid key contribute nothing
        p = jnp.where(valid.any(axis=1)[:, None], p, 0.0)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("mode", "window", "block_q", "block_k", "kv_offset",
                     "interpret"))
def flash_attention_packed_flat(q, k, v, segment_ids, *,
                                mode: str = "causal",
                                window: Optional[int] = None,
                                kv_segment_ids=None,
                                span_ids=None,
                                kv_span_ids=None,
                                block_q: int = DEFAULT_BLOCK_Q,
                                block_k: int = DEFAULT_BLOCK_K,
                                kv_offset: int = 0,
                                interpret: Optional[bool] = None
                                ) -> jax.Array:
    """Packed variable-length flash attention.

    q: [BH, Sq, D]; k/v: [BH, Sk, D]; segment_ids: [Sq] or [BH, Sq]
    int32, -1 for tail padding. `kv_segment_ids` defaults to
    `segment_ids` (self-attention); pass the neighbour's table for a
    ring hop together with its `kv_offset`. `span_ids`/`kv_span_ids`
    (same shapes, -1 = causal) mark bidirectional modality blocks —
    same-id tokens attend each other regardless of order, inside their
    segment; None means pure segment-causal masking.

    Rows whose segment never matches (tail padding) emit exact zeros.
    """
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    kv_seg = segment_ids if kv_segment_ids is None else kv_segment_ids
    kv_span = span_ids if kv_span_ids is None else kv_span_ids
    has_spans = kv_span is not None or span_ids is not None
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0))) if pad_k else v

    def _norm_seg(seg, length, pad, fill):
        if seg is None:
            return jnp.full((BH, length + pad), fill, jnp.int32)
        seg = jnp.asarray(seg, jnp.int32)
        if seg.ndim == 1:
            seg = jnp.broadcast_to(seg[None], (BH, length))
        return jnp.pad(seg, ((0, 0), (0, pad)), constant_values=fill)

    # Query-side tables go in as columns [BH, Sq, 1] and key-side ones
    # as rows [BH, 1, Sk]: Mosaic takes a block whose last two dims are
    # (8k, 128k) or the full array dims, which (1, block) over [BH, S]
    # is not; these give the kernel [bq, 1] x [1, bk] tiles that
    # broadcast straight into the [bq, bk] mask.
    def q_table(seg):
        return _norm_seg(seg, Sq, pad_q, -1)[:, :, None]

    def k_table(seg):
        return _norm_seg(seg, Sk, pad_k, -2)[:, None, :]
    nq = (Sq + pad_q) // block_q
    nk = (Sk + pad_k) // block_k

    kernel = functools.partial(
        _packed_kernel, mode=mode, window=window,
        sm_scale=1.0 / math.sqrt(D), block_q=block_q, block_k=block_k,
        kv_offset=kv_offset, has_spans=has_spans)

    q_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, j))
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        q_spec, k_spec,
    ]
    inputs = [qp, kp, vp, q_table(segment_ids), k_table(kv_seg)]
    if has_spans:
        # span tables only enter the kernel when a layout exists —
        # span-free callers keep the exact pre-span kernel program
        in_specs += [q_spec, k_spec]
        inputs += [q_table(span_ids), k_table(kv_span)]

    out = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq + pad_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # m
            pltpu.VMEM((block_q,), jnp.float32),      # l
            pltpu.VMEM((block_q, D), jnp.float32),    # acc
        ],
        interpret=interpret_mode(interpret),
    )(*inputs)
    return out[:, :Sq]


@functools.partial(
    jax.jit,
    static_argnames=("mode", "window", "block_q", "block_k", "kv_offset",
                     "interpret"))
def flash_attention_flat(q, k, v, *, mode: str = "causal",
                         window: Optional[int] = None,
                         block_q: int = DEFAULT_BLOCK_Q,
                         block_k: int = DEFAULT_BLOCK_K,
                         kv_offset: int = 0,
                         interpret: Optional[bool] = None) -> jax.Array:
    """q: [BH, Sq, D]; k/v: [BH, Sk, D] (KV pre-expanded to all heads).

    `interpret=None` interprets on the CPU backend and compiles
    elsewhere (`kernels.interpret_mode`)."""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0))) if pad_k else v
    nq = (Sq + pad_q) // block_q
    nk = (Sk + pad_k) // block_k

    kernel = functools.partial(
        _kernel, mode=mode, window=window, sm_scale=1.0 / math.sqrt(D),
        block_q=block_q, block_k=block_k, kv_offset=kv_offset, kv_len=Sk)

    out = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq + pad_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # m
            pltpu.VMEM((block_q,), jnp.float32),      # l
            pltpu.VMEM((block_q, D), jnp.float32),    # acc
        ],
        interpret=interpret_mode(interpret),
    )(qp, kp, vp)
    return out[:, :Sq]
