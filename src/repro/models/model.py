"""Top-level model API.

  init_params(key, cfg)                        -> params pytree
  forward(params, cfg, batch)                  -> (logits [B,S,V], aux)
  forward(..., with_routes=True)               -> (logits, aux, routes)
  init_cache(cfg, batch_size, cache_len)       -> decode cache pytree
  prefill_cache(params, cfg, batch, cache_len) -> cache  (audio cross-KV)
  decode_step(params, cfg, cache, tokens [B])  -> (logits [B,V], cache)

Batch dicts per family (all stub frontends produce *embeddings*):
  dense/moe/ssm/hybrid: {tokens}
  vlm:   {tokens, patch_embeds [B,P,Dv], patch_pos [B,P]}
  audio: {frames [B,F,D], tokens [B,S]}   (frames = conv-frontend stub)
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..parallel.act_sharding import constrain
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import ssm as ssm_mod
from .attention import attn_decode, attention, init_attention, \
    project_qkv_decode
from .layers import (_dtype, dense_init, embed, init_embedding,
                     init_layernorm, init_mlp, init_rmsnorm, layer_norm,
                     mlp, rms_norm, unembed)
from .transformer import (_BLOCK, _LAYER_INIT, _init_enc_layer,
                          _init_encdec_layer, _init_rec_layer,
                          _attn_kwargs, apply_stack, hybrid_layout,
                          init_stack)


def sinusoidal(seq: int, dim: int) -> jax.Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    inv = jnp.exp(-jnp.arange(0, dim, 2, jnp.float32)
                  * (jnp.log(10_000.0) / dim))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)[:, :dim]


def _sinusoidal_at(pos, dim: int) -> jax.Array:
    """One row of `sinusoidal` at a (traced) scalar position."""
    inv = jnp.exp(-jnp.arange(0, dim, 2, jnp.float32)
                  * (jnp.log(10_000.0) / dim))
    ang = jnp.asarray(pos, jnp.float32) * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)[:dim]


# ==========================================================================
# Init
# ==========================================================================
def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    dt = _dtype(cfg.param_dtype)
    k_emb, k_layers, k_head, k_extra = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "embed": init_embedding(k_emb, cfg.vocab, cfg.d_model, dt),
        "ln_f": init_rmsnorm(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(k_head, cfg.d_model, cfg.vocab, dt)

    if cfg.family in ("dense", "moe", "ssm", "vlm"):
        n_lead = cfg.moe.first_dense if cfg.moe else 0
        if n_lead:
            # a dense FFN leads the expert layers: its own stack
            k_lead, k_layers = jax.random.split(k_layers)
            params["dense_layers"] = init_stack(
                k_lead, cfg, n_lead, _LAYER_INIT["dense"])
        params["layers"] = init_stack(
            k_layers, cfg, cfg.n_layers - n_lead, _LAYER_INIT[cfg.family])
    elif cfg.family == "hybrid":
        n_units, tail = hybrid_layout(cfg)
        ku, kt = jax.random.split(k_layers)

        def init_unit(k):
            ks = jax.random.split(k, len(cfg.hybrid.pattern))
            unit = {}
            for i, kind in enumerate(cfg.hybrid.pattern):
                init = (_init_rec_layer if kind == "rec"
                        else _LAYER_INIT["dense"])
                unit[f"{i}_{kind}"] = init(ks[i], cfg)
            return unit

        params["units"] = jax.vmap(init_unit)(
            jax.random.split(ku, n_units))
        params["tail"] = {
            f"{i}_{kind}": (_init_rec_layer if kind == "rec"
                            else _LAYER_INIT["dense"])(
                jax.random.fold_in(kt, i), cfg)
            for i, kind in enumerate(tail)}
    elif cfg.family == "audio":
        ke, kd = jax.random.split(k_layers)
        params["enc_layers"] = init_stack(
            ke, cfg, cfg.encdec.n_enc_layers, _init_enc_layer)
        params["ln_enc"] = init_layernorm(cfg.d_model, dt)
        params["dec_layers"] = init_stack(
            kd, cfg, cfg.n_layers, _init_encdec_layer)
    else:
        raise ValueError(cfg.family)

    if cfg.family == "vlm":
        params["connector"] = dense_init(
            k_extra, cfg.vlm.vision_dim, cfg.d_model, dt)
    return params


def _check_servable(cfg: ModelConfig) -> None:
    if cfg.mla or (cfg.moe and cfg.moe.dropless):
        raise NotImplementedError(
            f"{cfg.arch_id}: serving (a KV or latent cache, and the "
            f"dropless expert layer at decode) is not implemented; this "
            f"model trains through Engine.train")


# ==========================================================================
# Embedding assembly (modality interleave)
# ==========================================================================
def _input_embeddings(params, cfg: ModelConfig, batch) -> jax.Array:
    x = constrain(embed(params["embed"], batch["tokens"]), "hidden")
    if cfg.family == "vlm":
        proj = batch["patch_embeds"].astype(x.dtype) @ params["connector"]
        x = jax.vmap(lambda e, p, pos: e.at[pos].set(p))(
            x, proj, batch["patch_pos"])
    return x


def _head(params, cfg: ModelConfig, x) -> jax.Array:
    x = constrain(rms_norm(params["ln_f"], x, cfg.norm_eps), "prehead")
    if cfg.tie_embeddings:
        return constrain(unembed(params["embed"], x, tied=True), "logits")
    return constrain(unembed(params["head"], x, tied=False), "logits")


# ==========================================================================
# Forward (train / prefill)
# ==========================================================================
def forward(params, cfg: ModelConfig, batch, mode: Optional[str] = None,
            with_routes: bool = False):
    """(logits, aux); `with_routes` adds the dropless expert layers'
    routes: the pairs routed to each held expert, [layers, experts]
    int32, or None where no layer routes."""
    if cfg.family == "audio":
        return _forward_audio(params, cfg, batch)
    x = _input_embeddings(params, cfg, batch)
    attn_mode = mode or ("sliding" if cfg.sliding_window else "causal")
    window = cfg.sliding_window
    positions = batch.get("positions")   # global positions (CP shards)
    # packed varlen: [B,S] segment table (-1 = tail padding); positions
    # reset per segment (core/packing.flatten_group produces both)
    segment_ids = batch.get("segment_ids")
    # mixed modality mask: [B,S] bidirectional-block table (-1 = causal
    # text / padding) — vision/audio spans attend forward within their
    # block (flatten_group / padded_batch produce it)
    span_ids = batch.get("modality_ids")

    routes = None
    if cfg.family in ("dense", "moe", "ssm", "vlm"):
        def stack(x, name, block):
            def body(p_l, h):
                return block(p_l, h, cfg, mode=attn_mode, window=window,
                             positions=positions, segment_ids=segment_ids,
                             span_ids=span_ids)
            return apply_stack(params[name], x, body, cfg.remat,
                               cfg.scan_layers)

        lead = None
        if "dense_layers" in params:
            x, lead = stack(x, "dense_layers", _BLOCK["dense"])
        x, aux, *ys = stack(x, "layers", _BLOCK[cfg.family])
        if lead is not None:
            aux = aux + lead
        routes = ys[0] if ys else None
    elif cfg.family == "hybrid":
        x, aux = _hybrid_forward(params, cfg, x, positions, segment_ids,
                                 span_ids)
    else:
        raise ValueError(cfg.family)
    if with_routes:
        return _head(params, cfg, x), aux, routes
    return _head(params, cfg, x), aux


def _hybrid_block(p_unit, x, cfg: ModelConfig, positions=None,
                  segment_ids=None, span_ids=None):
    from .transformer import _dense_block, _rec_block
    aux = jnp.zeros((), jnp.float32)
    for name in sorted(p_unit.keys()):
        kind = name.split("_")[1]
        if kind == "rec":
            x, a = _rec_block(p_unit[name], x, cfg)
        else:
            x, a = _dense_block(p_unit[name], x, cfg, mode="sliding",
                                window=cfg.hybrid.window,
                                positions=positions,
                                segment_ids=segment_ids,
                                span_ids=span_ids)
        aux = aux + a
    return x, aux


def _hybrid_forward(params, cfg: ModelConfig, x, positions=None,
                    segment_ids=None, span_ids=None):
    def body(p_unit, h):
        return _hybrid_block(p_unit, h, cfg, positions, segment_ids,
                             span_ids)
    x, aux = apply_stack(params["units"], x, body, cfg.remat,
                         cfg.scan_layers)
    x, a2 = _hybrid_block(params["tail"], x, cfg, positions, segment_ids,
                          span_ids)
    return x, aux + a2


def _forward_audio(params, cfg: ModelConfig, batch):
    from .transformer import _init_enc_layer  # noqa: F401
    frames = batch["frames"]
    B, F, _ = frames.shape
    enc = frames.astype(_dtype(cfg.param_dtype)) \
        + sinusoidal(F, cfg.d_model).astype(frames.dtype)

    def enc_block(p, h):
        g = layer_norm(p["ln1"], h, cfg.norm_eps)
        h = h + attention(p["attn"], g, **_attn_kwargs(cfg, "full"))
        g = layer_norm(p["ln2"], h, cfg.norm_eps)
        return h + mlp(p["mlp"], g, "gelu"), jnp.zeros((), jnp.float32)

    enc, _ = apply_stack(params["enc_layers"], enc, enc_block, cfg.remat,
                         cfg.scan_layers)
    enc = layer_norm(params["ln_enc"], enc, cfg.norm_eps)

    x = embed(params["embed"], batch["tokens"])
    S = x.shape[1]
    x = x + sinusoidal(S, cfg.d_model).astype(x.dtype)

    hd = cfg.resolved_head_dim

    def dec_block(p, h):
        g = layer_norm(p["ln1"], h, cfg.norm_eps)
        h = h + attention(p["attn"], g, **_attn_kwargs(cfg, "causal"))
        g = layer_norm(p["ln_x"], h, cfg.norm_eps)
        ck = (enc @ p["xattn"]["wk"]).reshape(B, F, cfg.kv_heads, hd)
        cv = (enc @ p["xattn"]["wv"]).reshape(B, F, cfg.kv_heads, hd)
        h = h + attention(p["xattn"], g, cross_kv=(ck, cv),
                          **_attn_kwargs(cfg, "full"))
        g = layer_norm(p["ln2"], h, cfg.norm_eps)
        return h + mlp(p["mlp"], g, "gelu"), jnp.zeros((), jnp.float32)

    x, aux = apply_stack(params["dec_layers"], x, dec_block, cfg.remat,
                         cfg.scan_layers)
    return _head(params, cfg, x), aux


# ==========================================================================
# Serving prefill (dense/moe/vlm): last-token logits + filled KV cache
# ==========================================================================
def prefill(params, cfg: ModelConfig, batch, cache_len: int | None = None):
    """Returns (last_logits [B,1,V], cache). Sliding-window archs keep a
    ring buffer holding the final `window` positions; full-attention
    caches are padded to `cache_len` capacity (default S + 1 headroom is
    NOT added — pass the serving capacity). For ssm/hybrid/audio use
    forward() + init_cache (logits-only prefill; see DESIGN.md).
    """
    assert cfg.family in ("dense", "moe", "vlm"), cfg.family
    _check_servable(cfg)
    x = _input_embeddings(params, cfg, batch)
    B, S, _ = x.shape
    positions = batch.get("positions")
    mode = "sliding" if cfg.sliding_window else "causal"
    from .transformer import _attn_kwargs as AK
    from .attention import attention as attn_fn
    from .layers import mlp as mlp_fn

    def block_kv(p, h):
        g = rms_norm(p["ln1"], h, cfg.norm_eps)
        o, (k, v) = attn_fn(p["attn"], g, positions=positions,
                            return_kv=True,
                            **AK(cfg, mode, cfg.sliding_window))
        h = h + o
        g = rms_norm(p["ln2"], h, cfg.norm_eps)
        if cfg.family == "moe":
            out, _ = moe_mod.moe_ffn(p["moe"], g, top_k=cfg.moe.top_k,
                                     capacity_factor=cfg.moe.capacity_factor,
                                     dispatch=cfg.moe.dispatch,
                                     dispatch_group=cfg.moe.dispatch_group)
        else:
            out = mlp_fn(p["mlp"], g, cfg.activation)
        return h + out, (k, v)

    def body(h, p_l):
        h = constrain(h, "hidden")
        fn = jax.checkpoint(block_kv) if cfg.remat else block_kv
        h, kv = fn(p_l, h)
        return h, kv

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    logits = _head(params, cfg, x[:, -1:])

    W = cfg.sliding_window
    if W is not None and W < S:
        # keep last W positions, rotated so slot(p) = p % W
        ks = jnp.roll(ks[:, :, S - W:], (S - W) % W, axis=2)
        vs = jnp.roll(vs[:, :, S - W:], (S - W) % W, axis=2)
    elif cache_len is not None and cache_len > S:
        pad = ((0, 0), (0, 0), (0, cache_len - S), (0, 0), (0, 0))
        ks = jnp.pad(ks, pad)
        vs = jnp.pad(vs, pad)
    cache = {"k": ks, "v": vs, "pos": jnp.asarray(S, jnp.int32)}
    return logits, cache


def prefill_chunk(params, cfg: ModelConfig, cache: Dict[str, Any],
                  tokens: jax.Array, start_pos, span_ids=None,
                  cache_span_ids=None) -> Dict[str, Any]:
    """Extend a full-attention KV cache by one prompt chunk.

    `tokens` [B, C] are prompt positions start_pos..start_pos+C-1;
    their K/V are written into cache rows [start_pos, start_pos+C) and
    each chunk token attends causally over everything written so far —
    the incremental step chunked prefill repeats until the prompt's KV
    is resident without ever materialising the O(L^2) one-shot prefill.

    `span_ids` [B,C] / `cache_span_ids` [B,T] (int32, -1 = causal)
    switch on the mixed modality mask: prompt tokens inside one
    bidirectional block (vision frame / audio window) attend each other
    regardless of order — exact when the serving scheduler keeps each
    block within one chunk (it snaps chunk boundaries to span ends).

    Requires a non-sliding cache (ring rotation would interleave chunk
    writes); dense/moe/vlm only. `start_pos` may be traced, so one
    compiled executable serves every chunk of every request at the same
    (B, C, T) bucket. Returns the updated cache with pos advanced by C
    (callers chunking a padded final bucket pass their own pos).
    """
    assert cfg.family in ("dense", "moe", "vlm"), cfg.family
    _check_servable(cfg)
    assert cfg.sliding_window is None, \
        "chunked prefill needs a non-rotating cache"
    from .attention import attn_prefill_chunk, project_qkv_decode  # noqa: F401
    from .layers import apply_rope

    x = _input_embeddings(params, cfg, {"tokens": tokens})
    B, C, _ = x.shape
    hd = cfg.resolved_head_dim
    rope_frac = (0.0 if not cfg.use_rope
                 else 0.5 if cfg.rope_2d else 1.0)
    positions = start_pos + jnp.arange(C)[None, :]     # [1,C] -> bcast B

    def block(p, h, ck, cv):
        # ck/cv: [B,T,Hkv,D] — one layer's cache rows
        g = rms_norm(p["ln1"], h, cfg.norm_eps)
        q = (g @ p["attn"]["wq"]).reshape(B, C, cfg.n_heads, hd)
        k = (g @ p["attn"]["wk"]).reshape(B, C, cfg.kv_heads, hd)
        v = (g @ p["attn"]["wv"]).reshape(B, C, cfg.kv_heads, hd)
        q = apply_rope(q, positions, cfg.rope_theta, rope_frac)
        k = apply_rope(k, positions, cfg.rope_theta, rope_frac)
        # drop-mode scatter, NOT dynamic_update_slice: a bucketed final
        # chunk may extend past the cache capacity, and the slice op
        # would clamp the start index and silently corrupt earlier rows
        rows = start_pos + jnp.arange(C)
        ck = ck.at[:, rows].set(k.astype(ck.dtype), mode="drop")
        cv = cv.at[:, rows].set(v.astype(cv.dtype), mode="drop")
        o = attn_prefill_chunk(q, ck, cv, start_pos,
                               chunk_span_ids=span_ids,
                               cache_span_ids=cache_span_ids)
        h = h + o.reshape(B, C, -1) @ p["attn"]["wo"]
        g = rms_norm(p["ln2"], h, cfg.norm_eps)
        if cfg.family == "moe":
            out, _ = moe_mod.moe_ffn(
                p["moe"], g, top_k=cfg.moe.top_k,
                capacity_factor=cfg.moe.capacity_factor,
                dispatch=cfg.moe.dispatch,
                dispatch_group=cfg.moe.dispatch_group)
        else:
            out = mlp(p["mlp"], g, cfg.activation)
        return h + out, ck, cv

    def body(h, xs):
        p_l, ck, cv = xs
        h, ck, cv = block(p_l, h, ck, cv)
        return h, (ck, cv)

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"]))
    return {**cache, "k": ks, "v": vs,
            "pos": jnp.asarray(start_pos + C, jnp.int32)}


# ==========================================================================
# Decode caches
# ==========================================================================
def _kv_shape(cfg, n_layers, batch, cache_len):
    return (n_layers, batch, cache_len, cfg.kv_heads, cfg.resolved_head_dim)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=None) -> Dict[str, Any]:
    """cache_len = context capacity; sliding-window archs allocate only
    min(window, cache_len) slots (ring buffer)."""
    _check_servable(cfg)
    dt = dtype or _dtype(cfg.param_dtype)
    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.family in ("dense", "moe", "vlm"):
        T = min(cfg.sliding_window or cache_len, cache_len)
        cache["k"] = jnp.zeros(_kv_shape(cfg, cfg.n_layers, batch, T), dt)
        cache["v"] = jnp.zeros(_kv_shape(cfg, cfg.n_layers, batch, T), dt)
    elif cfg.family == "ssm":
        s = cfg.ssm
        d_inner = s.expand * cfg.d_model
        H = d_inner // s.head_dim
        L = cfg.n_layers
        cache["h"] = jnp.zeros((L, batch, H, s.d_state, s.head_dim),
                               jnp.float32)
        cache["conv_buf"] = jnp.zeros(
            (L, batch, s.conv_width - 1, d_inner + 2 * s.d_state), dt)
    elif cfg.family == "hybrid":
        n_units, tail = hybrid_layout(cfg)
        h = cfg.hybrid
        W = h.lru_width or cfg.d_model
        n_rec_per_unit = sum(k == "rec" for k in h.pattern)
        n_attn_per_unit = sum(k == "attn" for k in h.pattern)
        T = min(h.window, cache_len)
        cache["rec_h"] = jnp.zeros((n_units, n_rec_per_unit, batch, W),
                                   jnp.float32)
        cache["rec_conv"] = jnp.zeros(
            (n_units, n_rec_per_unit, batch, h.conv_width - 1, W), dt)
        cache["k"] = jnp.zeros(
            (n_units, n_attn_per_unit, batch, T, cfg.kv_heads,
             cfg.resolved_head_dim), dt)
        cache["v"] = jnp.zeros_like(cache["k"])
        n_rec_tail = sum(k == "rec" for k in tail)
        cache["tail_h"] = jnp.zeros((max(n_rec_tail, 1), batch, W),
                                    jnp.float32)
        cache["tail_conv"] = jnp.zeros(
            (max(n_rec_tail, 1), batch, h.conv_width - 1, W), dt)
    elif cfg.family == "audio":
        T = min(cfg.sliding_window or cache_len, cache_len)
        L = cfg.n_layers
        cache["k"] = jnp.zeros(_kv_shape(cfg, L, batch, T), dt)
        cache["v"] = jnp.zeros(_kv_shape(cfg, L, batch, T), dt)
        F = cfg.encdec.n_audio_frames
        cache["cross_k"] = jnp.zeros(_kv_shape(cfg, L, batch, F), dt)
        cache["cross_v"] = jnp.zeros(_kv_shape(cfg, L, batch, F), dt)
    else:
        raise ValueError(cfg.family)
    return cache


def prefill_cross_kv(params, cfg: ModelConfig, frames,
                     cache: Dict[str, Any]) -> Dict[str, Any]:
    """Audio: run the encoder once, fill per-layer cross K/V."""
    B, F, _ = frames.shape

    def enc_block(p, h):
        g = layer_norm(p["ln1"], h, cfg.norm_eps)
        h = h + attention(p["attn"], g, **_attn_kwargs(cfg, "full"))
        g = layer_norm(p["ln2"], h, cfg.norm_eps)
        return h + mlp(p["mlp"], g, "gelu"), jnp.zeros((), jnp.float32)

    enc = frames.astype(_dtype(cfg.param_dtype)) \
        + sinusoidal(F, cfg.d_model).astype(frames.dtype)
    enc, _ = apply_stack(params["enc_layers"], enc, enc_block, cfg.remat,
                         cfg.scan_layers)
    enc = layer_norm(params["ln_enc"], enc, cfg.norm_eps)
    hd = cfg.resolved_head_dim

    def per_layer(p):
        ck = (enc @ p["xattn"]["wk"]).reshape(B, F, cfg.kv_heads, hd)
        cv = (enc @ p["xattn"]["wv"]).reshape(B, F, cfg.kv_heads, hd)
        return ck, cv

    ck, cv = jax.vmap(per_layer)(params["dec_layers"])
    return {**cache, "cross_k": ck, "cross_v": cv}


# ==========================================================================
# Decode step
# ==========================================================================
def _write_kv(cache_k, cache_v, k1, v1, pos):
    """Ring-buffer write at slot pos % T. k1: [B,1,Hkv,D]."""
    T = cache_k.shape[1]
    slot = pos % T
    ck = jax.lax.dynamic_update_slice_in_dim(cache_k, k1.astype(
        cache_k.dtype), slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache_v, v1.astype(
        cache_v.dtype), slot, axis=1)
    return ck, cv


def _dense_decode_layer(p, x1, ck, cv, pos, cfg: ModelConfig):
    B = x1.shape[0]
    h = rms_norm(p["ln1"], x1[:, None], cfg.norm_eps)[:, 0]
    q, k1, v1 = project_qkv_decode(
        p["attn"], h, n_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
        head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
        position=jnp.full((B,), pos),
        rope_frac=0.5 if cfg.rope_2d else 1.0)
    ck, cv = _write_kv(ck, cv, k1, v1, pos)
    T = ck.shape[1]
    valid = jnp.minimum(pos + 1, T)
    o = attn_decode(q, ck, cv, jnp.full((B,), valid))
    x1 = x1 + (o.reshape(B, -1) @ p["attn"]["wo"])
    h = rms_norm(p["ln2"], x1[:, None], cfg.norm_eps)
    if cfg.family == "moe" or ("moe" in p):
        out, _ = moe_mod.moe_ffn(p["moe"], h, top_k=cfg.moe.top_k,
                                 capacity_factor=cfg.moe.capacity_factor,
                                 dispatch=cfg.moe.dispatch,
                                 dispatch_group=cfg.moe.dispatch_group)
    else:
        out = mlp(p["mlp"], h, cfg.activation)
    return x1 + out[:, 0], ck, cv


def decode_step(params, cfg: ModelConfig, cache: Dict[str, Any],
                tokens: jax.Array) -> Tuple[jax.Array, Dict[str, Any]]:
    """tokens: [B] -> (logits [B,V], updated cache)."""
    pos = cache["pos"]
    x1 = embed(params["embed"], tokens)
    if cfg.family == "audio":
        x1 = x1 + _sinusoidal_at(pos, cfg.d_model).astype(x1.dtype)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(x, xs):
            p_l, ck, cv = xs
            x, ck, cv = _dense_decode_layer(p_l, x, ck, cv, pos, cfg)
            return x, (ck, cv)
        x1, (ck, cv) = jax.lax.scan(
            body, x1, (params["layers"], cache["k"], cache["v"]))
        cache = {**cache, "k": ck, "v": cv}
    elif cfg.family == "ssm":
        s = cfg.ssm
        def body(x, xs):
            p_l, h_l, cb_l = xs
            g = rms_norm(p_l["ln1"], x[:, None], cfg.norm_eps)[:, 0]
            y, st = ssm_mod.ssm_decode_step(
                p_l["ssm"], g, {"h": h_l, "conv_buf": cb_l},
                d_state=s.d_state, head_dim=s.head_dim, expand=s.expand)
            return x + y, (st["h"], st["conv_buf"])
        x1, (h, cb) = jax.lax.scan(
            body, x1, (params["layers"], cache["h"], cache["conv_buf"]))
        cache = {**cache, "h": h, "conv_buf": cb}
    elif cfg.family == "hybrid":
        x1, cache = _hybrid_decode(params, cfg, cache, x1, pos)
    elif cfg.family == "audio":
        def body(x, xs):
            p_l, ck, cv, xk, xv = xs
            B = x.shape[0]
            x, ck, cv = _audio_decode_self(p_l, x, ck, cv, pos, cfg)
            g = layer_norm(p_l["ln_x"], x[:, None], cfg.norm_eps)[:, 0]
            q = (g @ p_l["xattn"]["wq"]).reshape(
                B, 1, cfg.n_heads, cfg.resolved_head_dim)
            F = xk.shape[1]
            o = attn_decode(q, xk, xv, jnp.full((B,), F))
            x = x + o.reshape(B, -1) @ p_l["xattn"]["wo"]
            g = layer_norm(p_l["ln2"], x[:, None], cfg.norm_eps)
            x = x + mlp(p_l["mlp"], g, "gelu")[:, 0]
            return x, (ck, cv)
        x1, (ck, cv) = jax.lax.scan(
            body, x1, (params["dec_layers"], cache["k"], cache["v"],
                       cache["cross_k"], cache["cross_v"]))
        cache = {**cache, "k": ck, "v": cv}
    else:
        raise ValueError(cfg.family)

    logits = _head(params, cfg, x1[:, None])[:, 0]
    return logits, {**cache, "pos": pos + 1}


def _audio_decode_self(p, x1, ck, cv, pos, cfg: ModelConfig):
    B = x1.shape[0]
    h = layer_norm(p["ln1"], x1[:, None], cfg.norm_eps)[:, 0]
    q = (h @ p["attn"]["wq"]).reshape(B, 1, cfg.n_heads,
                                      cfg.resolved_head_dim)
    k1 = (h @ p["attn"]["wk"]).reshape(B, 1, cfg.kv_heads,
                                       cfg.resolved_head_dim)
    v1 = (h @ p["attn"]["wv"]).reshape(B, 1, cfg.kv_heads,
                                       cfg.resolved_head_dim)
    ck, cv = _write_kv(ck, cv, k1, v1, pos)
    T = ck.shape[1]
    valid = jnp.minimum(pos + 1, T)
    o = attn_decode(q, ck, cv, jnp.full((B,), valid))
    x1 = x1 + o.reshape(B, -1) @ p["attn"]["wo"]
    return x1, ck, cv


def _hybrid_decode(params, cfg: ModelConfig, cache, x1, pos):
    h_cfg = cfg.hybrid
    pattern = h_cfg.pattern
    rec_ids = [i for i, k in enumerate(pattern) if k == "rec"]
    attn_ids = [i for i, k in enumerate(pattern) if k == "attn"]

    def unit_body(x, xs):
        p_u, rh, rc, ck, cv = xs
        new_rh, new_rc, new_ck, new_cv = [], [], [], []
        ri = ai = 0
        for name in sorted(p_u.keys()):
            kind = name.split("_")[1]
            if kind == "rec":
                g = rms_norm(p_u[name]["ln1"], x[:, None], cfg.norm_eps)[:, 0]
                y, st = rglru_mod.rglru_decode_step(
                    p_u[name]["rec"], g,
                    {"h": rh[ri], "conv_buf": rc[ri]})
                x = x + y
                g = rms_norm(p_u[name]["ln2"], x[:, None], cfg.norm_eps)
                x = x + mlp(p_u[name]["mlp"], g, cfg.activation)[:, 0]
                new_rh.append(st["h"])
                new_rc.append(st["conv_buf"])
                ri += 1
            else:
                x, k_new, v_new = _dense_decode_layer(
                    p_u[name], x, ck[ai], cv[ai], pos, cfg)
                new_ck.append(k_new)
                new_cv.append(v_new)
                ai += 1
        return x, (jnp.stack(new_rh), jnp.stack(new_rc),
                   jnp.stack(new_ck), jnp.stack(new_cv))

    x1, (rh, rc, ck, cv) = jax.lax.scan(
        unit_body, x1,
        (params["units"], cache["rec_h"], cache["rec_conv"],
         cache["k"], cache["v"]))

    # tail (rec layers)
    th, tc = [], []
    ti = 0
    for name in sorted(params["tail"].keys()):
        kind = name.split("_")[1]
        p_l = params["tail"][name]
        if kind == "rec":
            g = rms_norm(p_l["ln1"], x1[:, None], cfg.norm_eps)[:, 0]
            y, st = rglru_mod.rglru_decode_step(
                p_l["rec"], g,
                {"h": cache["tail_h"][ti], "conv_buf": cache["tail_conv"][ti]})
            x1 = x1 + y
            g = rms_norm(p_l["ln2"], x1[:, None], cfg.norm_eps)
            x1 = x1 + mlp(p_l["mlp"], g, cfg.activation)[:, 0]
            th.append(st["h"])
            tc.append(st["conv_buf"])
            ti += 1
    new_cache = {**cache, "rec_h": rh, "rec_conv": rc, "k": ck, "v": cv}
    if th:
        new_cache["tail_h"] = jnp.stack(th)
        new_cache["tail_conv"] = jnp.stack(tc)
    return x1, new_cache
