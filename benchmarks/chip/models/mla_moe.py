"""A DeepSeek-V3-style decoder held as one chip of an expert-parallel
deployment (Kimi-VL-A3B's language model): latent attention (MLA), a
leading dense layer, then expert layers that hold a share of the
experts. The program's `family="moe"` model with `mla` and a dropless
`moe`, as the benchmark sizes it, makes its weights, checks it and
counts its FLOPs.

Named by a configuration's `"model": "mla_moe"`. The sizes are the
configuration's own keys, those of the model's config.json:
`num_hidden_layers` (of them `first_k_dense_replace` dense),
`hidden_size`, `num_attention_heads`, `kv_lora_rank`,
`qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`,
`intermediate_size` (the dense FFN), `moe_intermediate_size`,
`n_shared_experts`, `n_routed_experts` (the experts held here, from
`expert_offset`), `router_experts` (the router's width),
`num_experts_per_tok`, `routed_scaling_factor`, `rms_norm_eps`,
`rope_theta` and `vocab` (the chosen weights normalised: `norm_topk_prob`
true). The latent's RMSNorm epsilon, which config.json does not give,
is the modelling code's default, `LATENT_EPS`.

The parameter layout, the program's interface (Ld dense layers, Lm
expert layers, H heads, r the latent, dn/dr/dv the head parts):

    embed [V, D]   head [D, V]   ln_f.scale [D]
    dense_layers, layers: ln1.scale, ln2.scale [L, D]
        attn.wq [L, D, H*(dn+dr)]   attn.wkv_a [L, D, r+dr]
        attn.kv_norm.scale [L, r]   attn.wkv_b [L, r, H*(dn+dv)]
        attn.wo [L, H*dv, D]
    dense_layers: mlp.up, mlp.gate [Ld, D, F]   mlp.down [Ld, F, D]
    layers: moe.router [Lm, D, R]   moe.router_bias [Lm, R]
        moe.gate, moe.up [Lm, E, D, Fe]   moe.down [Lm, E, Fe, D]
        moe.shared.up, .gate [Lm, D, S*Fe]   moe.shared.down [Lm, S*Fe, D]

The plain forward pass (`row_loss`), written from the configuration
alone (it imports nothing of the program): RMSNorm; MLA (q = h·wq per
head [nope ‖ rope], rope on the rope dims; [c, k_rope] = h·wkv_a,
c RMS-normed, one roped k_rope for all heads; [k_nope ‖ v] = c·wkv_b;
scores over sqrt(dn + dr)) under the mixed mask (causal, plus
bidirectional blocks, never across sequences); the dense layer's SwiGLU;
in each expert layer, sigmoid scores over all R experts, the top k of
score + bias chosen, the chosen scores normalised and scaled, and each
held expert's SwiGLU computed on EVERY token of the row, times its
weight (zero where it was not chosen), plus the shared experts; untied
LM head; next-token NLL over the labels whose token is not inside a
bidirectional block. The experts held elsewhere add nothing, as in the
program. Attention runs per block of queries and the loss per block of
tokens, each recomputed in the backward pass.

Model FLOPs (`token_flops`, `pair_flops`): 6 x the matmul parameters a
token passes through (each layer's attention, the dense layers' FFN, the
expert layers' shared experts, router and top_k x held / routed expert
widths, the LM head), forward 2 and backward 4. 6 x H x (dn + dr + dv)
an attended (query, key) pair and layer: QK^T and PV forward, three
times that with the backward.

The grouped matmuls (`gmm_flops`, `gmm_bytes`): the work the expert
layer's Pallas `gmm`/`tgmm` kernels do for the pairs routed to held
experts: three projections forward, again under remat, `gmm` for the
input's gradient and `tgmm` for the weights', each 2 x D x Fe a pair.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from models.dense_gqa import HIGHEST, LOSS_BLOCK, QUERY_BLOCK, _block, \
    _rms, _rope

#: the correction bias, seeded small and non-zero: with scores spread
#: over ~0.2 and the 6th and 7th of 64 ~0.01 apart, it moves the choice
ROUTER_BIAS_STD = 0.02
INIT = {"layers/moe/router_bias": lambda key, shape, dtype: (
    jax.random.normal(key, shape, jnp.float32) * ROUTER_BIAS_STD
).astype(dtype)}
#: grouped-matmul kernel calls a pair goes through in a training step
GMM_PASSES = 12
#: epsilon of the latent's RMSNorm (DeepSeek-V2's kv_a_layernorm default)
LATENT_EPS = 1e-6


def _sizes(cfg: Dict) -> Dict:
    H = cfg["num_attention_heads"]
    dn, dr, dv = (cfg[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                   "v_head_dim"))
    return {"L": cfg["num_hidden_layers"], "Ld": cfg["first_k_dense_replace"],
            "D": cfg["hidden_size"], "H": H, "r": cfg["kv_lora_rank"],
            "dn": dn, "dr": dr, "dv": dv, "F": cfg["intermediate_size"],
            "Fe": cfg["moe_intermediate_size"],
            "Fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "E": cfg["n_routed_experts"], "R": cfg["router_experts"],
            "k": cfg["num_experts_per_tok"], "V": cfg["vocab"]}


def program_config(cfg: Dict):
    """The program's `ModelConfig` for this configuration."""
    import dataclasses

    from repro.configs import get_config
    base = get_config(cfg["arch"])
    s = _sizes(cfg)
    if (cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]
            or not cfg["norm_topk_prob"]):
        raise ValueError("mla_moe models untied SiLU-gated decoders whose "
                         "chosen expert weights are normalised")
    return base.with_(
        family="moe", n_layers=s["L"], d_model=s["D"], n_heads=s["H"],
        kv_heads=s["H"], d_ff=s["F"], vocab=s["V"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        param_dtype=cfg["param_dtype"], remat=cfg["remat"],
        activation="swiglu", tie_embeddings=False,
        mla=dataclasses.replace(
            base.mla, kv_lora_rank=s["r"], qk_nope_head_dim=s["dn"],
            qk_rope_head_dim=s["dr"], v_head_dim=s["dv"]),
        moe=dataclasses.replace(
            base.moe, n_experts=s["E"], router_experts=s["R"],
            expert_offset=cfg["expert_offset"], top_k=s["k"],
            expert_ff=s["Fe"], n_shared=cfg["n_shared_experts"],
            scoring=cfg["scoring_func"],
            route_scale=cfg["routed_scaling_factor"], first_dense=s["Ld"]))


def shapes(cfg: Dict) -> Dict:
    s = _sizes(cfg)
    D, H, V = s["D"], s["H"], s["V"]

    def attn(L):
        return {"wq": (L, D, H * (s["dn"] + s["dr"])),
                "wkv_a": (L, D, s["r"] + s["dr"]),
                "kv_norm": {"scale": (L, s["r"])},
                "wkv_b": (L, s["r"], H * (s["dn"] + s["dv"])),
                "wo": (L, H * s["dv"], D)}

    def swiglu(*lead, f):
        return {"up": (*lead, D, f), "gate": (*lead, D, f),
                "down": (*lead, f, D)}

    Ld, Lm = s["Ld"], s["L"] - s["Ld"]
    norms = lambda L: {"ln1": {"scale": (L, D)}, "ln2": {"scale": (L, D)}}  # noqa: E731
    return {
        "embed": (V, D), "head": (D, V), "ln_f": {"scale": (D,)},
        "dense_layers": {**norms(Ld), "attn": attn(Ld),
                         "mlp": swiglu(Ld, f=s["F"])},
        "layers": {**norms(Lm), "attn": attn(Lm), "moe": {
            "router": (Lm, D, s["R"]), "router_bias": (Lm, s["R"]),
            **swiglu(Lm, s["E"], f=s["Fe"]),
            "shared": swiglu(Lm, f=s["Fs"])}},
    }


# --------------------------------------------------------------------------
# the plain forward pass
# --------------------------------------------------------------------------
def _attention(q, k, v, row):
    """q/k [S, H, Dqk], v [S, H, Dv]; per block of queries, under the
    mask of `dense_gqa`: causal, or in the same bidirectional block, and
    in the same sequence; padding rows attend nothing."""
    S, H, dqk = q.shape
    q = q / math.sqrt(dqk)
    blk = _block(QUERY_BLOCK, S)
    seg, block = row["seg"], row["block"]
    idx = jnp.arange(S)

    @jax.checkpoint
    def one(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * blk, blk)  # noqa: E731
        qs, qi, sq, bq = sl(q), sl(idx), sl(seg), sl(block)
        s = jnp.einsum("shd,thd->sht", qs, k, precision=HIGHEST)
        allowed = ((qi[:, None] >= idx[None, :])
                   | ((bq[:, None] >= 0) & (bq[:, None] == block[None])))
        allowed &= (sq[:, None] == seg[None]) & (sq[:, None] >= 0)
        p = jax.nn.softmax(jnp.where(allowed[:, None, :], s, -1e30))
        p = p * jnp.any(allowed, -1)[:, None, None]
        return jnp.einsum("sht,thd->shd", p, v, precision=HIGHEST)

    out = jax.lax.map(one, jnp.arange(S // blk))
    return out.reshape(S, H * v.shape[-1])


def _mla(h, w, row, cfg, s, mm):
    S, H, dn, dr = h.shape[0], s["H"], s["dn"], s["dr"]
    q = mm(h, w["wq"]).reshape(S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], row["pos"],
                                            cfg["rope_theta"])], -1)
    kv_a = mm(h, w["wkv_a"])
    c = _rms(kv_a[:, :s["r"]], w["kv_norm"]["scale"], LATENT_EPS)
    k_rope = _rope(kv_a[:, None, s["r"]:], row["pos"], cfg["rope_theta"])
    kv = mm(c, w["wkv_b"]).reshape(S, H, dn + s["dv"])
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rope, (S, H, dr))], -1)
    return mm(_attention(q, k, kv[..., dn:], row), w["wo"])


def _swiglu(h, w, mm):
    a = mm(h, w["gate"])
    return mm(a * jax.nn.sigmoid(a) * mm(h, w["up"]), w["down"])


def gates(h, w, cfg, mm):
    """[S, R] weight of every expert for every token: the chosen ones'
    sigmoid scores, normalised and scaled; zero where not chosen."""
    scores = jax.nn.sigmoid(mm(h, w["router"]))
    _, idx = jax.lax.top_k(scores + w["router_bias"],
                           cfg["num_experts_per_tok"])
    chosen = jax.nn.one_hot(idx, scores.shape[-1]).sum(1)
    g = scores * chosen
    g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return g * cfg["routed_scaling_factor"]


def _experts(h, w, cfg, s, mm):
    """The held experts on every token, each under its weight, plus the
    shared experts."""
    off = cfg["expert_offset"]
    g = gates(h, w, cfg, mm)[:, off:off + s["E"]]              # [S, E]
    a = mm(h[None], w["gate"])                                  # [E, S, Fe]
    y = mm(a * jax.nn.sigmoid(a) * mm(h[None], w["up"]), w["down"])
    return jnp.einsum("se,esd->sd", g, y, precision=HIGHEST) \
        + _swiglu(h, w["shared"], mm)


def row_loss(p, row, cfg, mm):
    """Sum of the row's labelled next-token NLL. `mm` is the reference's
    matmul (float32, or the float8 control's)."""
    s = _sizes(cfg)
    eps = cfg["rms_norm_eps"]
    x = p["embed"][row["tokens"]]

    def stack(ffn):
        @jax.checkpoint
        def layer(x, w):
            x = x + _mla(_rms(x, w["ln1"]["scale"], eps), w["attn"], row,
                         cfg, s, mm)
            return x + ffn(_rms(x, w["ln2"]["scale"], eps), w), None
        return layer

    x, _ = jax.lax.scan(stack(lambda h, w: _swiglu(h, w["mlp"], mm)), x,
                        p["dense_layers"])
    x, _ = jax.lax.scan(stack(lambda h, w: _experts(h, w["moe"], cfg, s,
                                                    mm)), x, p["layers"])
    h = _rms(x, p["ln_f"]["scale"], eps)
    blk = _block(LOSS_BLOCK, h.shape[0])

    @jax.checkpoint
    def loss_block(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * blk, blk)  # noqa: E731
        logits = mm(sl(h), p["head"])
        gold = jnp.take_along_axis(logits, sl(row["labels"])[:, None],
                                   -1)[:, 0]
        nll = jax.nn.logsumexp(logits, -1) - gold
        return jnp.sum(nll * sl(row["lmask"]))

    return jnp.sum(jax.lax.map(loss_block, jnp.arange(h.shape[0] // blk)))


# --------------------------------------------------------------------------
# model FLOPs, and the grouped matmuls' work
# --------------------------------------------------------------------------
def attn_matmul_params(cfg: Dict) -> int:
    s = _sizes(cfg)
    D, H = s["D"], s["H"]
    return (D * H * (s["dn"] + s["dr"]) + D * (s["r"] + s["dr"])
            + s["r"] * H * (s["dn"] + s["dv"]) + H * s["dv"] * D)


def expert_layer_active_params(cfg: Dict) -> float:
    """Shared experts, router, and the share of a token's top_k experts
    held here (top_k x held / routed expert widths)."""
    s = _sizes(cfg)
    expert = 3 * s["D"] * s["Fe"]
    return (3 * s["D"] * s["Fs"] + s["D"] * s["R"]
            + s["k"] * s["E"] / s["R"] * expert)


def token_flops(cfg: Dict) -> float:
    s = _sizes(cfg)
    return 6 * (s["L"] * attn_matmul_params(cfg)
                + s["Ld"] * 3 * s["D"] * s["F"]
                + (s["L"] - s["Ld"]) * expert_layer_active_params(cfg)
                + s["D"] * s["V"])


def pair_flops(cfg: Dict) -> int:
    s = _sizes(cfg)
    return 6 * s["H"] * (s["dn"] + s["dr"] + s["dv"]) * s["L"]


def expert_layers(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def gmm_flops(cfg: Dict, pairs: float) -> float:
    """FLOPs of the grouped-matmul kernels for `pairs` (token, slot)
    pairs routed to held experts, summed over the expert layers: 12
    kernel calls a pair (3 projections x forward, remat, gmm for dx,
    tgmm for dW), 2 x D x Fe each."""
    s = _sizes(cfg)
    return GMM_PASSES * 2 * s["D"] * s["Fe"] * pairs


def gmm_bytes(cfg: Dict, pairs: float, calls: int) -> float:
    """Least HBM bytes of those kernels: each of the 12 calls of a layer
    and group reads its pairs' rows and one held expert's matrix per
    expert and writes its pairs' rows, in bf16; `calls` is the number of
    (expert layer, group) pairs."""
    s = _sizes(cfg)
    rows = 2 * (s["D"] + s["Fe"])              # in + out row of one pass
    weights = 2 * s["E"] * s["D"] * s["Fe"]    # one projection's experts
    return GMM_PASSES // 3 * (3 * rows * pairs + 3 * weights * calls)

