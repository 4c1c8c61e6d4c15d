"""Pallas TPU kernel: blocked RG-LRU linear scan.

h_t = a_t * h_{t-1} + b_t, evaluated chunk-by-chunk: the grid's
sequential axis walks sequence chunks, a VMEM scratch carries the running
state across chunks, and within a chunk the recurrence closes via a small
log2(chunk) Hillis-Steele pass over VREG-resident tiles. The channel axis
is tiled to the 128-lane VPU width.

This is the TPU adaptation of Griffin's CUDA linear-scan kernel: instead
of warp shuffles, we exploit the VPU's full-width elementwise throughput
and keep the carried state in VMEM scratch between grid steps.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode


def _kernel(a_ref, b_ref, h_ref, carry_scr, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        carry_scr[...] = jnp.zeros_like(carry_scr)

    a = a_ref[0].astype(jnp.float32)          # [chunk, w]
    b = b_ref[0].astype(jnp.float32)

    # Hillis-Steele inclusive scan of the affine maps within the chunk
    step = 1
    while step < chunk:
        a_prev = jnp.concatenate(
            [jnp.ones((step, a.shape[1]), jnp.float32), a[:-step]], axis=0)
        b_prev = jnp.concatenate(
            [jnp.zeros((step, b.shape[1]), jnp.float32), b[:-step]], axis=0)
        b = a * b_prev + b
        a = a * a_prev
        step *= 2

    h0 = carry_scr[...]                        # [1, w] carried state
    h = a * h0 + b                             # close over previous chunks
    h_ref[0] = h.astype(h_ref.dtype)
    carry_scr[...] = h[-1:]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def rglru_scan_pallas(a, b, *, chunk: int = 128,
                      interpret: Optional[bool] = None) -> jax.Array:
    """a, b: [B, S, W] -> h: [B, S, W] with h_t = a_t h_{t-1} + b_t."""
    B, S, W = a.shape
    pad = (-S) % chunk
    if pad:
        # identity padding: a=1, b=0 keeps the state unchanged
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
    nc = (S + pad) // chunk
    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(B, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, W), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, W), lambda bi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, W), lambda bi, ci: (bi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S + pad, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, W), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(a, b)
    return out[:, :S]
