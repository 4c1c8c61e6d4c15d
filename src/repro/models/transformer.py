"""Transformer assembly: per-family blocks + scan-over-layers stacking.

Layer parameters are STACKED along a leading [L] axis and consumed with
`jax.lax.scan`, so the compiled HLO contains one layer's program
regardless of depth — essential for the 512-device dry-runs of a
126-layer model. `cfg.remat` wraps the block body in `jax.checkpoint`.

Families:
  dense  — [attn + MLP] x L                     (llama3/glm4/chatglm3/minitron/pixtral LM)
  moe    — [attn + MoE-FFN] x L                 (granite, olmoe)
           [MLA + MLP] x first_dense, then [MLA + dropless held-share
           experts + shared experts] x the rest  (kimi-vl-a3b)
  ssm    — [mamba2 SSD] x L                     (mamba2-370m)
  hybrid — [(rec, rec, attn) + MLP each] x ...  (recurrentgemma)
  audio  — whisper enc(full attn) + dec(causal + cross)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..parallel.act_sharding import constrain
from . import attention as attn_mod
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import ssm as ssm_mod
from .attention import attention, attn_decode, init_attention, \
    project_qkv_decode
from .layers import (_dtype, dense_init, embed, init_embedding, init_mlp,
                     init_rmsnorm, init_layernorm, layer_norm, mlp,
                     rms_norm, unembed)


# ==========================================================================
# Per-layer init (vmapped over layer keys -> stacked params)
# ==========================================================================
def _init_attn(key, cfg: ModelConfig, dt):
    if cfg.mla:
        return attn_mod.init_mla(key, cfg.d_model, cfg.n_heads, cfg.mla, dt)
    return init_attention(key, cfg.d_model, cfg.n_heads, cfg.kv_heads,
                          cfg.resolved_head_dim, dt)


def _init_dense_layer(key, cfg: ModelConfig):
    dt = _dtype(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    return {
        "ln1": init_rmsnorm(cfg.d_model, dt),
        "attn": _init_attn(k1, cfg, dt),
        "ln2": init_rmsnorm(cfg.d_model, dt),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.activation, dt),
    }


def _init_moe_layer(key, cfg: ModelConfig):
    dt = _dtype(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    moe = (moe_mod.init_moe_dropless(k2, cfg.d_model, cfg.moe, dt)
           if cfg.moe.dropless else
           moe_mod.init_moe(k2, cfg.d_model, cfg.moe.n_experts,
                            cfg.moe.expert_ff, dt))
    return {
        "ln1": init_rmsnorm(cfg.d_model, dt),
        "attn": _init_attn(k1, cfg, dt),
        "ln2": init_rmsnorm(cfg.d_model, dt),
        "moe": moe,
    }


def _init_ssm_layer(key, cfg: ModelConfig):
    dt = _dtype(cfg.param_dtype)
    s = cfg.ssm
    return {
        "ln1": init_rmsnorm(cfg.d_model, dt),
        "ssm": ssm_mod.init_ssm(key, cfg.d_model, d_state=s.d_state,
                                head_dim=s.head_dim, expand=s.expand,
                                conv_width=s.conv_width, dtype=dt),
    }


def _init_rec_layer(key, cfg: ModelConfig):
    dt = _dtype(cfg.param_dtype)
    h = cfg.hybrid
    k1, k2 = jax.random.split(key)
    return {
        "ln1": init_rmsnorm(cfg.d_model, dt),
        "rec": rglru_mod.init_rglru_block(
            k1, cfg.d_model, h.lru_width or cfg.d_model, h.conv_width, dt),
        "ln2": init_rmsnorm(cfg.d_model, dt),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.activation, dt),
    }


def _init_enc_layer(key, cfg: ModelConfig):
    dt = _dtype(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    return {
        "ln1": init_layernorm(cfg.d_model, dt),
        "attn": init_attention(k1, cfg.d_model, cfg.n_heads, cfg.kv_heads,
                               cfg.resolved_head_dim, dt),
        "ln2": init_layernorm(cfg.d_model, dt),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, "gelu", dt),
    }


def _init_encdec_layer(key, cfg: ModelConfig):
    dt = _dtype(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": init_layernorm(cfg.d_model, dt),
        "attn": init_attention(k1, cfg.d_model, cfg.n_heads, cfg.kv_heads,
                               cfg.resolved_head_dim, dt),
        "ln_x": init_layernorm(cfg.d_model, dt),
        "xattn": init_attention(k2, cfg.d_model, cfg.n_heads, cfg.kv_heads,
                                cfg.resolved_head_dim, dt),
        "ln2": init_layernorm(cfg.d_model, dt),
        "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, "gelu", dt),
    }


_LAYER_INIT = {
    "dense": _init_dense_layer,
    "moe": _init_moe_layer,
    "ssm": _init_ssm_layer,
    "vlm": _init_dense_layer,
}


# ==========================================================================
# Block apply fns: (params_l, x, ctx) -> (x, aux)
# ==========================================================================
def _attn_kwargs(cfg: ModelConfig, mode: str, window=None):
    return dict(n_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
                head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
                rope_frac=(0.0 if not cfg.use_rope
                           else 0.5 if cfg.rope_2d else 1.0),
                impl=cfg.attn_impl, mode=mode, window=window,
                cp_axis=cfg.cp_axis)


def _attn(p, h, cfg: ModelConfig, mode, window, positions, segment_ids,
          span_ids):
    if cfg.mla:
        return attn_mod.mla_attention(
            p, h, n_heads=cfg.n_heads, mla=cfg.mla,
            rope_theta=cfg.rope_theta, positions=positions, mode=mode,
            window=window, impl=cfg.attn_impl, cp_axis=cfg.cp_axis,
            segment_ids=segment_ids, span_ids=span_ids)
    return attention(p, h, positions=positions, segment_ids=segment_ids,
                     span_ids=span_ids, **_attn_kwargs(cfg, mode, window))


def _dense_block(p, x, cfg: ModelConfig, mode="causal", window=None,
                 positions=None, segment_ids=None, span_ids=None):
    h = rms_norm(p["ln1"], x, cfg.norm_eps)
    x = x + _attn(p["attn"], h, cfg, mode, window, positions, segment_ids,
                  span_ids)
    h = rms_norm(p["ln2"], x, cfg.norm_eps)
    x = x + mlp(p["mlp"], h, cfg.activation)
    return x, jnp.zeros((), jnp.float32)


def _moe_block(p, x, cfg: ModelConfig, mode="causal", window=None,
               positions=None, segment_ids=None, span_ids=None):
    """The dropless layer returns (x, aux, pairs per held expert); the
    capacity layer (x, its load-balance loss)."""
    h = rms_norm(p["ln1"], x, cfg.norm_eps)
    x = x + _attn(p["attn"], h, cfg, mode, window, positions, segment_ids,
                  span_ids)
    h = rms_norm(p["ln2"], x, cfg.norm_eps)
    if cfg.moe.dropless:
        out, pairs = moe_mod.moe_dropless(
            p["moe"], h, cfg.moe,
            token_mask=None if segment_ids is None else segment_ids >= 0)
        return x + out, jnp.zeros((), jnp.float32), pairs
    out, aux = moe_mod.moe_ffn(p["moe"], h, top_k=cfg.moe.top_k,
                               capacity_factor=cfg.moe.capacity_factor,
                               dispatch=cfg.moe.dispatch,
                               dispatch_group=cfg.moe.dispatch_group)
    return x + out, aux


def _ssm_block(p, x, cfg: ModelConfig, **_):
    s = cfg.ssm
    h = rms_norm(p["ln1"], x, cfg.norm_eps)
    x = x + ssm_mod.ssm_forward(
        p["ssm"], h, d_state=s.d_state, head_dim=s.head_dim,
        expand=s.expand, chunk=s.chunk,
        impl="pallas" if cfg.attn_impl == "pallas" else "jnp")
    return x, jnp.zeros((), jnp.float32)


def _rec_block(p, x, cfg: ModelConfig, **_):
    h = rms_norm(p["ln1"], x, cfg.norm_eps)
    x = x + rglru_mod.rglru_block(p["rec"], h)
    h = rms_norm(p["ln2"], x, cfg.norm_eps)
    x = x + mlp(p["mlp"], h, cfg.activation)
    return x, jnp.zeros((), jnp.float32)


_BLOCK = {"dense": _dense_block, "moe": _moe_block, "ssm": _ssm_block,
          "vlm": _dense_block}


# ==========================================================================
# Stacks
# ==========================================================================
def init_stack(key, cfg: ModelConfig, n_layers: int, init_fn):
    keys = jax.random.split(key, n_layers)
    return jax.vmap(lambda k: init_fn(k, cfg))(keys)


def apply_stack(stacked, x, block_fn, remat: bool, scan: bool = True):
    """Scan x through stacked layer params, accumulating aux losses. A
    block that returns (h, aux, y) has its y stacked over the layers:
    the stack then returns (x, aux, ys)."""
    def body(carry, p_l):
        h, aux = carry
        h = constrain(h, "hidden")
        fn = jax.checkpoint(block_fn) if remat else block_fn
        h, a, *y = fn(p_l, h)
        return (h, aux + a), (y[0] if y else None)

    if scan:
        (x, aux), ys = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), stacked)
    else:
        aux = jnp.zeros((), jnp.float32)
        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        ys = []
        for i in range(n):
            p_l = jax.tree.map(lambda a: a[i], stacked)
            (x, aux), y = body((x, aux), p_l)
            ys.append(y)
        ys = None if ys[0] is None else jnp.stack(ys)
    return (x, aux) if ys is None else (x, aux, ys)


# ==========================================================================
# Hybrid (RecurrentGemma) layout helpers
# ==========================================================================
def hybrid_layout(cfg: ModelConfig) -> Tuple[int, Tuple[str, ...]]:
    """(n_full_units, tail_block_types). 26 layers @ (rec,rec,attn) ->
    8 full units + ('rec','rec') tail."""
    unit = cfg.hybrid.pattern
    n_units = cfg.n_layers // len(unit)
    tail = cfg.n_layers - n_units * len(unit)
    return n_units, unit[:tail]
