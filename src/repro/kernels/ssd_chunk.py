"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk step.

The chunked SSD algorithm (arXiv:2405.21060, models/ssm.py) evaluates
the recurrence inside each length-`c` chunk in its dual quadratic form.
The hot spot is per (batch·head, chunk):

    cum      = cumsum(dt·A)                       [c]
    L[i,j]   = exp(cum_i − cum_j) · 1[i ≥ j]      [c,c]   (decay mask)
    scores   = (C Bᵀ) ∘ L ∘ dt_j                  [c,c]
    y_intra  = scores · x                         [c,P]
    states   = (B ∘ dt ∘ exp(cum_c − cum))ᵀ · x   [N,P]   (chunk summary)

On GPU this is where Mamba-2 fuses into a single kernel so the [c,c]
matrices never hit HBM; the TPU-native adaptation is the same fusion
with MXU-shaped tiles: one grid cell = one (bh, chunk), all [c,N]/[c,P]
blocks resident in VMEM (c = 128–256, N = 128, P = 64–128 ⇒ ≤ 0.6 MB of
fp32 per cell), the two matmuls hit the 128×128 systolic array, and only
y_intra / states / cum are written back. The O(S/c) inter-chunk state
scan stays outside (it is tiny: [N,P] per head) — see
`ops.ssd_chunk_scan` for the composed op.

Validated against `ref.ssd_chunk_ref` in interpret mode (CPU).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret_mode


def _kernel(c_ref, b_ref, x_ref, da_ref, dt_ref,
            y_ref, st_ref, cum_ref):
    C = c_ref[0].astype(jnp.float32)       # [c, N]
    B = b_ref[0].astype(jnp.float32)       # [c, N]
    x = x_ref[0].astype(jnp.float32)       # [c, P]
    da = da_ref[0].astype(jnp.float32)     # [c]
    dt = dt_ref[0].astype(jnp.float32)     # [c]
    c = C.shape[0]

    cum = jnp.cumsum(da)                                    # [c]
    diff = cum[:, None] - cum[None, :]                      # [c,c]
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    L = jnp.where(i >= j, jnp.exp(diff), 0.0)               # decay mask
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    scores = cb * L * dt[None, :]                           # [c,c]
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    decay_end = jnp.exp(cum[-1] - cum) * dt                 # [c]
    st = jax.lax.dot_general(B * decay_end[:, None], x,
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [N,P]
    y_ref[0] = y.astype(y_ref.dtype)
    st_ref[0] = st.astype(st_ref.dtype)
    cum_ref[0] = cum.astype(cum_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_pallas(C, B, x, da, dt, *,
                     interpret: Optional[bool] = None):
    """Intra-chunk SSD for a batch of independent chunks.

    C, B: [G, c, N]; x: [G, c, P]; da, dt: [G, c]
      (G = batch · heads · n_chunks flattened; da = dt·A)
    Returns (y_intra [G,c,P], states [G,N,P], cum [G,c]) in fp32.
    """
    G, c, N = C.shape
    P = x.shape[-1]
    return pl.pallas_call(
        _kernel,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((1, c, N), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, c, N), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, c, P), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, c), lambda g: (g, 0)),
            pl.BlockSpec((1, c), lambda g: (g, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, P), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, N, P), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, c), lambda g: (g, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, c, P), jnp.float32),
            jax.ShapeDtypeStruct((G, N, P), jnp.float32),
            jax.ShapeDtypeStruct((G, c), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(C, B, x, da, dt)
