"""A dense GQA decoder: the program's `family="dense"` model, as the
benchmark sizes it, makes its weights, checks it and counts its FLOPs.

Named by a configuration's `"model": "dense_gqa"`. The sizes are the
configuration's `n_layers`, `d_model`, `n_heads`, `kv_heads`,
`head_dim`, `d_ff` and `vocab`.

The parameter layout, the program's interface (a dict of stacked
layers):

    embed [V, D]   head [D, V]   ln_f.scale [D]
    layers: ln1.scale, ln2.scale [L, D]
            attn.wq [L, D, H*Dh]  attn.wk, attn.wv [L, D, Hkv*Dh]
            attn.wo [L, H*Dh, D]
            mlp.up, mlp.gate [L, D, F]   mlp.down [L, F, D]

The plain forward pass (`row_loss`), written from the configuration
alone (it imports nothing of the program): RMSNorm, rotary q/k,
grouped-query attention under the mixed mask (causal, plus
bidirectional blocks, never across sequences), SwiGLU MLP, untied LM
head, next-token NLL over the labels whose token is not inside a
bidirectional block. Attention runs per block of queries and the loss
per block of tokens, each block recomputed in the backward pass.

Model FLOPs (`token_flops`, `pair_flops`): 6 x (the matmul parameters
of every layer + the LM head) a token, forward 2 and backward 4; the
embedding lookup is free. 12 x heads x head_dim an attended (query,
key) pair and layer: QK^T and PV, 2 x 2 x H x Dh forward, three times
that with the backward.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 1024
LOSS_BLOCK = 512
#: keys of a configuration file that size the program's model
PROGRAM_KEYS = ("n_layers", "d_model", "n_heads", "kv_heads", "head_dim",
                "d_ff", "vocab", "rope_theta", "norm_eps", "param_dtype",
                "remat", "activation", "tie_embeddings")


def program_config(cfg: Dict):
    """The program's `ModelConfig` for this configuration."""
    from repro.configs import get_config
    return get_config(cfg["arch"]).with_(
        family="dense", vlm=None, **{k: cfg[k] for k in PROGRAM_KEYS})


def shapes(cfg: Dict) -> Dict:
    L, D, F, V = (cfg[k] for k in ("n_layers", "d_model", "d_ff", "vocab"))
    q = cfg["n_heads"] * cfg["head_dim"]
    kv = cfg["kv_heads"] * cfg["head_dim"]
    return {
        "embed": (V, D), "head": (D, V), "ln_f": {"scale": (D,)},
        "layers": {
            "ln1": {"scale": (L, D)}, "ln2": {"scale": (L, D)},
            "attn": {"wq": (L, D, q), "wk": (L, D, kv), "wv": (L, D, kv),
                     "wo": (L, q, D)},
            "mlp": {"up": (L, D, F), "gate": (L, D, F), "down": (L, F, D)},
        },
    }


# --------------------------------------------------------------------------
# the plain forward pass
# --------------------------------------------------------------------------
def _block(size: int, n: int) -> int:
    blk = min(size, n)
    if n % blk:
        raise ValueError(f"row of {n} tokens is not a multiple of {blk}")
    return blk


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta):
    """Rotary embedding on pairs of dims (2i, 2i+1), the program's
    layout. x [S, heads, Dh], pos [S]."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attention(q, k, v, row):
    """q [S, H, Dh], k/v [S, Hkv, Dh]; per block of queries."""
    S, H, dh = q.shape
    hkv = k.shape[1]
    qg = q.reshape(S, hkv, H // hkv, dh) / math.sqrt(dh)
    blk = _block(QUERY_BLOCK, S)
    seg, block = row["seg"], row["block"]
    idx = jnp.arange(S)

    @jax.checkpoint
    def one(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * blk, blk)
        qs, qi, sq, bq = sl(qg), sl(idx), sl(seg), sl(block)
        s = jnp.einsum("skgd,tkd->skgt", qs, k, precision=HIGHEST)
        allowed = ((qi[:, None] >= idx[None, :])
                   | ((bq[:, None] >= 0) & (bq[:, None] == block[None])))
        allowed &= (sq[:, None] == seg[None]) & (sq[:, None] >= 0)
        p = jax.nn.softmax(jnp.where(allowed[:, None, None, :], s, -1e30))
        # padding rows attend nothing: zero, not a uniform average
        p = p * jnp.any(allowed, -1)[:, None, None, None]
        return jnp.einsum("skgt,tkd->skgd", p, v, precision=HIGHEST)

    out = jax.lax.map(one, jnp.arange(S // blk))
    return out.reshape(S, H * dh)


def row_loss(p, row, cfg, mm):
    """Sum of the row's labelled next-token NLL. `mm` is the reference's
    matmul (float32, or the float8 control's)."""
    if cfg["activation"] != "swiglu" or cfg.get("tie_embeddings"):
        raise ValueError("dense_gqa models untied SwiGLU decoders")
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    H, hkv, dh = cfg["n_heads"], cfg["kv_heads"], cfg["head_dim"]
    S = row["tokens"].shape[0]
    x = p["embed"][row["tokens"]]

    @jax.checkpoint
    def layer(x, w):
        h = _rms(x, w["ln1"]["scale"], eps)
        q = _rope(mm(h, w["attn"]["wq"]).reshape(S, H, dh), row["pos"],
                  theta)
        k = _rope(mm(h, w["attn"]["wk"]).reshape(S, hkv, dh), row["pos"],
                  theta)
        v = mm(h, w["attn"]["wv"]).reshape(S, hkv, dh)
        x = x + mm(_attention(q, k, v, row), w["attn"]["wo"])
        h = _rms(x, w["ln2"]["scale"], eps)
        a = mm(h, w["mlp"]["gate"])
        x = x + mm(a * jax.nn.sigmoid(a) * mm(h, w["mlp"]["up"]),
                   w["mlp"]["down"])
        return x, None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    h = _rms(x, p["ln_f"]["scale"], eps)
    blk = _block(LOSS_BLOCK, S)

    @jax.checkpoint
    def loss_block(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * blk, blk)
        logits = mm(sl(h), p["head"])
        gold = jnp.take_along_axis(logits, sl(row["labels"])[:, None],
                                   -1)[:, 0]
        nll = jax.nn.logsumexp(logits, -1) - gold
        return jnp.sum(nll * sl(row["lmask"]))

    return jnp.sum(jax.lax.map(loss_block, jnp.arange(S // blk)))


# --------------------------------------------------------------------------
# model FLOPs
# --------------------------------------------------------------------------
def layer_matmul_params(cfg: Dict) -> int:
    d, dh = cfg["d_model"], cfg["head_dim"]
    attn = d * dh * (2 * cfg["n_heads"] + 2 * cfg["kv_heads"])
    n_mlp = 3 if cfg["activation"] == "swiglu" else 2
    return attn + n_mlp * d * cfg["d_ff"]


def token_flops(cfg: Dict) -> int:
    return 6 * (cfg["n_layers"] * layer_matmul_params(cfg)
                + cfg["d_model"] * cfg["vocab"])


def pair_flops(cfg: Dict) -> int:
    return 12 * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"]
