"""jit'd public wrappers around the Pallas kernels.

`flash_attention` takes the model-layer layout [B, S, H, D] with GQA
KV [B, S, Hkv, D], expands KV groups, flattens (batch, head) and
dispatches to the kernel (or the jnp reference when ref=True).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import (flash_attention_flat,
                              flash_attention_packed_flat)
from .ref import (flash_attention_packed_ref, flash_attention_ref,
                  ssd_chunk_ref)
from .rglru_scan import rglru_scan_pallas
from .ssd_chunk import ssd_chunk_pallas


def _expand_gqa(k: jax.Array, n_heads: int) -> jax.Array:
    B, S, Hkv, D = k.shape
    G = n_heads // Hkv
    return jnp.repeat(k, G, axis=2)


@partial(jax.jit,
         static_argnames=("mode", "window", "ref", "interpret", "block_q",
                          "block_k"))
def flash_attention(q, k, v, *, mode: str = "causal",
                    window: Optional[int] = None, ref: bool = False,
                    interpret: Optional[bool] = None, block_q: int = 128,
                    block_k: int = 128) -> jax.Array:
    """q: [B,S,H,D]; k/v: [B,S,Hkv,D] -> [B,S,H,D]."""
    B, Sq, H, D = q.shape
    k = _expand_gqa(k, H)
    v = _expand_gqa(v, H)
    Sk = k.shape[1]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)
    if ref:
        of = flash_attention_ref(qf, kf, vf, mode=mode, window=window)
    else:
        of = flash_attention_flat(qf, kf, vf, mode=mode, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)
    return of.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


@partial(jax.jit,
         static_argnames=("mode", "window", "ref", "interpret", "block_q",
                          "block_k"))
def flash_attention_packed(q, k, v, segment_ids, *, mode: str = "causal",
                          window: Optional[int] = None,
                          span_ids=None, ref: bool = False,
                          interpret: Optional[bool] = None,
                          block_q: int = 128,
                          block_k: int = 128) -> jax.Array:
    """Packed varlen attention in model layout.

    q: [B,S,H,D]; k/v: [B,S,Hkv,D]; segment_ids: [B,S] or [S] int32
    (-1 = tail padding) -> [B,S,H,D]. Each batch row is an independent
    packed buffer; attention is block-diagonal over its segments.
    `span_ids` (same shape convention, -1 = causal) marks bidirectional
    modality blocks for the mixed mask.
    """
    B, Sq, H, D = q.shape
    k = _expand_gqa(k, H)
    v = _expand_gqa(v, H)
    Sk = k.shape[1]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)

    def _norm(t):
        if t is None:
            return None
        t = jnp.asarray(t, jnp.int32)
        if t.ndim == 2:                     # [B,S] -> [B*H, S]
            t = jnp.repeat(t, H, axis=0)
        return t

    seg = _norm(segment_ids)
    span = _norm(span_ids)
    if ref:
        if seg.ndim == 1 and (span is None or span.ndim == 1):
            of = flash_attention_packed_ref(qf, kf, vf, seg, mode=mode,
                                            window=window, span_ids=span)
        else:
            seg2 = jnp.broadcast_to(seg, (B * H, Sk)) \
                if seg.ndim == 1 else seg
            if span is None:
                of = jax.vmap(
                    lambda qq, kk, vv, ss: flash_attention_packed_ref(
                        qq[None], kk[None], vv[None], ss, mode=mode,
                        window=window)[0])(qf, kf, vf, seg2)
            else:
                span2 = jnp.broadcast_to(span, (B * H, Sk)) \
                    if span.ndim == 1 else span
                of = jax.vmap(
                    lambda qq, kk, vv, ss, pp: flash_attention_packed_ref(
                        qq[None], kk[None], vv[None], ss, mode=mode,
                        window=window, span_ids=pp)[0])(
                    qf, kf, vf, seg2, span2)
    else:
        of = flash_attention_packed_flat(qf, kf, vf, seg, mode=mode,
                                         window=window, span_ids=span,
                                         block_q=block_q,
                                         block_k=block_k,
                                         interpret=interpret)
    return of.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("ref", "interpret"))
def ssd_chunk_scan(C, B, x, da, dt, *, ref: bool = False,
                   interpret: Optional[bool] = None):
    """Full chunked-SSD output for independent sequences of chunks.

    C, B: [G, nc, c, N]; x: [G, nc, c, P]; da, dt: [G, nc, c]
      (G = batch·heads; nc chunks of length c per sequence).
    Returns y [G, nc, c, P] fp32 — intra-chunk term from the Pallas
    kernel (or jnp oracle with ref=True) + inter-chunk term from the
    O(nc) state scan, exactly the models/ssm.py decomposition.
    """
    G, nc, c, N = C.shape
    P = x.shape[-1]
    flat = lambda t: t.reshape((G * nc,) + t.shape[2:])   # noqa: E731
    fn = ssd_chunk_ref if ref else partial(ssd_chunk_pallas,
                                           interpret=interpret)
    y_intra, states, cum = fn(flat(C), flat(B), flat(x), flat(da),
                              flat(dt))
    y_intra = y_intra.reshape(G, nc, c, P)
    states = states.reshape(G, nc, N, P)
    cum = cum.reshape(G, nc, c)
    seg_end = cum[..., -1]                                 # [G,nc]

    def scan_fn(h, inp):
        st, dec = inp
        return h * jnp.exp(dec)[:, None, None] + st, h     # emit PREV
    _, h_prev = jax.lax.scan(
        scan_fn, jnp.zeros((G, N, P), jnp.float32),
        (states.transpose(1, 0, 2, 3), seg_end.transpose(1, 0)))
    h_prev = h_prev.transpose(1, 0, 2, 3)                  # [G,nc,N,P]
    y_inter = jnp.einsum("gcin,gcnp->gcip", C.astype(jnp.float32),
                         h_prev) * jnp.exp(cum)[..., None]
    return y_intra + y_inter


__all__ = ["flash_attention", "flash_attention_packed",
           "rglru_scan_pallas", "ssd_chunk_pallas", "ssd_chunk_scan"]
