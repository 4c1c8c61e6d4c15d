"""Mixture-of-Experts FFN: top-k router + capacity-bounded dispatch, and
the dropless expert layer over a held share of the experts.

Expert weights are stacked [E, d_model, d_ff] so the `model` mesh axis
shards the EXPERT dimension (expert parallelism) — XLA then inserts the
all-to-all-equivalent collectives for the dispatch/combine einsums.
Dispatch uses the standard capacity-factor one-hot formulation (tokens
over capacity are dropped, residual passthrough keeps them alive), plus
the switch-style load-balance auxiliary loss.

`moe_dropless` is DeepSeek-V3's layer (`noaux_tc`), as one chip of an
expert-parallel deployment runs it: the router scores every expert, the
layer holds `n_experts` of them and computes, with no capacity and no
drop, their part of the result for every (token, slot) pair routed to
them, as grouped matmuls (Pallas `megablox.gmm`; its VJP brings `tgmm`)
over the pairs sorted by expert. The shared experts run on every token.
What the experts held elsewhere add is left out: on one chip the layer
runs without its exchange.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from .layers import dense_init, init_mlp, mlp


def init_moe(key, d_model: int, n_experts: int, expert_ff: int,
             dtype) -> dict:
    kr, kg, ku, kd = jax.random.split(key, 4)
    return {
        "router": dense_init(kr, d_model, n_experts, jnp.float32),
        "gate": (jax.random.normal(kg, (n_experts, d_model, expert_ff),
                                   jnp.float32) / jnp.sqrt(d_model)
                 ).astype(dtype),
        "up": (jax.random.normal(ku, (n_experts, d_model, expert_ff),
                                 jnp.float32) / jnp.sqrt(d_model)
               ).astype(dtype),
        "down": (jax.random.normal(kd, (n_experts, expert_ff, d_model),
                                   jnp.float32) / jnp.sqrt(expert_ff)
                 ).astype(dtype),
    }


def moe_ffn(params: dict, x: jax.Array, *, top_k: int,
            capacity_factor: float = 1.25,
            dispatch: str = "sort",
            dispatch_group: int = 0,
            ) -> Tuple[jax.Array, jax.Array]:
    """x: [B,S,D] -> (out [B,S,D], aux_loss scalar).

    `dispatch`:
      "sort"   — argsort-based gather/scatter dispatch, O(T·k·D) data
                 movement and zero dispatch FLOPs (the TPU-native path;
                 see EXPERIMENTS.md §Perf iteration G1).
      "einsum" — classic Mesh-TF one-hot formulation: builds a
                 [T,E,cap] dispatch tensor, whose einsums cost
                 O(T·E·cap·D) = O(T²·D·k·cf/1) FLOPs — quadratic in
                 tokens. Kept as the reference/baseline.
    Both paths implement identical capacity semantics (first-come
    queueing in token order, dropped tokens ride the residual).
    """
    B, S, D = x.shape
    E = params["router"].shape[1]
    T = B * S
    xt = x.reshape(T, D)

    logits = (xt.astype(jnp.float32) @ params["router"])      # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, top_k)               # [T,k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # load-balance loss (Switch): E * sum_e f_e * p_e
    me = probs.mean(0)                                         # [E]
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(
        1.0 / (T * top_k))
    aux = E * jnp.sum(me * ce)

    cap = int(capacity_factor * T * top_k / E) or 1

    if dispatch == "sort":
        # Dispatch in groups of <= dispatch_group tokens. Group
        # boundaries align with the batch/sequence sharding (B·S
        # flatten), so each group's argsort/scatter stays shard-local —
        # the global variant all-gathers the whole [E·cap, D] expert
        # buffer across the data axis (§Perf iteration G2). Capacity is
        # per-group (cap_g = cf·Tg·k/E), the same semantics at
        # dispatch_group >= T as the global einsum reference.
        Tg = dispatch_group or T
        while T % Tg:                     # largest divisor <= requested
            Tg -= 1
        G = T // Tg
        cap_g = int(capacity_factor * Tg * top_k / E) or 1
        out = jax.vmap(
            lambda xg, ig, gg: _moe_sort_dispatch(params, xg, ig, gg,
                                                  cap_g)
        )(xt.reshape(G, Tg, D), idx.reshape(G, Tg, top_k),
          gate_vals.reshape(G, Tg, top_k))
        return out.reshape(B, S, D), aux

    # ---------------- reference einsum path ----------------
    # position of each (token, slot) within its expert's queue
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)           # [T,k,E]
    flat = onehot.reshape(T * top_k, E)
    pos = jnp.cumsum(flat, axis=0) - 1                         # queue index
    pos = (pos * flat).sum(-1).reshape(T, top_k)               # [T,k]
    keep = pos < cap
    gate_vals = gate_vals * keep

    # dispatch [T,k,E,cap] one-hot (bool) -> expert inputs [E,cap,D]
    disp = (jax.nn.one_hot(idx, E, dtype=xt.dtype)[..., :, None]
            * jax.nn.one_hot(pos, cap, dtype=xt.dtype)[..., None, :]
            * keep[..., None, None])                           # [T,k,E,cap]
    disp = disp.sum(1)                                         # [T,E,cap]
    ex_in = jnp.einsum("td,tec->ecd", xt, disp)                # [E,cap,D]

    ex_out = _expert_mlps(params, ex_in)                       # [E,cap,D]

    comb = jnp.einsum("tec,ecd->ted", disp, ex_out)            # [T,E,D]
    # weighted combine: sum_k gate * expert_out(token)
    gate_e = (jax.nn.one_hot(idx, E, dtype=xt.dtype)
              * gate_vals[..., None].astype(xt.dtype)).sum(1)  # [T,E]
    out = jnp.einsum("te,ted->td", gate_e, comb)
    return out.reshape(B, S, D), aux


def _expert_mlps(params: dict, ex_in: jax.Array) -> jax.Array:
    """[E,cap,D] -> [E,cap,D] through each expert's SwiGLU MLP."""
    h = jnp.einsum("ecd,edf->ecf", ex_in, params["gate"])
    u = jnp.einsum("ecd,edf->ecf", ex_in, params["up"])
    h = jax.nn.silu(h) * u
    return jnp.einsum("ecf,efd->ecd", h, params["down"])       # [E,cap,D]


def _moe_sort_dispatch(params: dict, xt: jax.Array, idx: jax.Array,
                       gate_vals: jax.Array, cap: int) -> jax.Array:
    """Sort-based dispatch: gather tokens into [E,cap,D] expert buffers
    via a stable argsort over expert ids — no [T,E,cap] tensor, no
    dispatch matmuls. Identical capacity semantics to the one-hot path
    (queue position = arrival order of (token, slot) pairs)."""
    T, D = xt.shape
    E = params["router"].shape[1]
    k = idx.shape[1]
    S = T * k                                                  # slots

    slot_expert = idx.reshape(S)                               # [S]
    order = jnp.argsort(slot_expert, stable=True)              # [S]
    # rank of each slot in the sorted order, then queue position
    # within its expert = rank - (# slots of smaller expert ids)
    rank = jnp.zeros((S,), jnp.int32).at[order].set(
        jnp.arange(S, dtype=jnp.int32))
    counts = jnp.bincount(slot_expert, length=E)               # [E]
    starts = jnp.cumsum(counts) - counts                       # [E]
    pos = rank - starts[slot_expert]                           # [S]
    keep = pos < cap
    gate_kept = (gate_vals.reshape(S) * keep).astype(xt.dtype)

    # scatter tokens into expert buffers (unique (e,pos) per kept slot)
    buf_idx = jnp.where(keep, slot_expert * cap + pos, E * cap)  # drop row
    token_of_slot = jnp.arange(S, dtype=jnp.int32) // k
    ex_in = jnp.zeros((E * cap + 1, D), xt.dtype).at[buf_idx].set(
        xt[token_of_slot], mode="drop")
    ex_out = _expert_mlps(params, ex_in[:E * cap].reshape(E, cap, D))

    # gather back: each kept slot reads its expert-buffer row
    slot_out = ex_out.reshape(E * cap, D)[
        jnp.clip(buf_idx, 0, E * cap - 1)]                     # [S,D]
    slot_out = slot_out * gate_kept[:, None]
    out = jnp.zeros((T, D), xt.dtype).at[token_of_slot].add(slot_out)
    return out


# --------------------------------------------------------------------------
# Dropless expert layer over a held share (sigmoid scores + bias, top-k)
# --------------------------------------------------------------------------
#: rows of a grouped-matmul tile; the pair buffer is padded to it
GMM_ROWS = 512


def init_moe_dropless(key, d_model: int, moe, dtype) -> dict:
    """router [D, R] over all R experts; router_bias [R], the correction
    bias (zero here; DeepSeek-V3 moves it by a rule outside the
    gradient); gate/up [E, D, F] and down [E, F, D] of the E experts
    held; shared, one SwiGLU n_shared * F wide."""
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    E, F = moe.n_experts, moe.expert_ff

    def experts(k, a, b):
        return (jax.random.normal(k, (E, a, b), jnp.float32)
                / jnp.sqrt(a)).astype(dtype)

    p = {"router": dense_init(kr, d_model, moe.n_routed, dtype),
         "router_bias": jnp.zeros((moe.n_routed,), dtype),
         "gate": experts(kg, d_model, F), "up": experts(ku, d_model, F),
         "down": experts(kd, F, d_model)}
    if moe.n_shared:
        p["shared"] = init_mlp(ks, d_model, moe.n_shared * F, "swiglu",
                               dtype)
    return p


def route(params: dict, xt: jax.Array, moe) -> Tuple[jax.Array, jax.Array]:
    """(experts [T,k], weights [T,k] float32): scores s = sigmoid(x·W_r)
    over all experts in float32; the top k of s + bias chosen; their s,
    normalised to sum 1, times `route_scale`."""
    scores = jax.nn.sigmoid(jnp.dot(
        xt, params["router"].astype(xt.dtype),
        preferred_element_type=jnp.float32))
    _, idx = jax.lax.top_k(
        scores + params["router_bias"].astype(jnp.float32), moe.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * moe.route_scale


def _gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """Tiles of 512 where a dim divides by 512, else the whole dim where
    it is a multiple of 128 up to 1536 (an expert width of 1408), else
    128: within the default scoped VMEM, double-buffered."""
    def tile(d):
        if d % 512 == 0:
            return 512
        return d if (d % 128 == 0 and d <= 1536) or d < 128 else 128
    return GMM_ROWS, tile(k), tile(n)


def grouped_matmul(lhs, rhs, group_sizes):
    """lhs [m, K] rows (m a multiple of `GMM_ROWS`) grouped by
    `group_sizes` [E + 1] (the last group is never computed: its rows
    come back zero) times rhs [E, K, N]."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from ..kernels import interpret_mode
    return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, _gmm_tiling,
                        None, None, False, interpret_mode())


def moe_dropless(params: dict, x: jax.Array, moe, token_mask=None
                 ) -> Tuple[jax.Array, jax.Array]:
    """x [B,S,D] -> (out [B,S,D], pairs [E] int32 routed to each held
    expert). `token_mask` ([B,S] bool) routes no pair of a token outside
    it (the packed buffer's tail padding).

    The T·k (token, slot) pairs are sorted by held expert, those of
    experts held elsewhere last; the buffer has the worst case's rows
    (every pair held here), padded to a tile, so its shape never depends
    on the routing and no step recompiles; the group sizes say how many
    rows each expert computes. Each pair's output comes back to its
    token under its weight; the shared experts are added."""
    B, S, D = x.shape
    T, k, E = B * S, moe.top_k, moe.n_experts
    xt = x.reshape(T, D)
    idx, w = route(params, xt, moe)
    local = idx - moe.expert_offset
    held = (local >= 0) & (local < E)
    if token_mask is not None:
        held &= token_mask.reshape(T, 1)
    expert = jnp.where(held, local, E).reshape(T * k)
    order = jnp.argsort(expert, stable=True)
    n_pad = (-T * k) % GMM_ROWS
    sizes = jnp.bincount(expert, length=E + 1).astype(jnp.int32)
    sizes = sizes.at[E].add(n_pad)
    rows = jnp.pad(order // k, (0, n_pad))
    gmm = partial(grouped_matmul, group_sizes=sizes)
    xs = jnp.take(xt, rows, axis=0)
    h = gmm(xs, params["gate"])
    ys = gmm(jax.nn.silu(h) * gmm(xs, params["up"]), params["down"])
    # each pair's row, back in (token, slot) order
    slot_row = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    y = jnp.take(ys, slot_row, axis=0).reshape(T, k, D)
    out = jnp.sum(jnp.where(held, w, 0.0)[..., None]
                  * y.astype(jnp.float32), axis=1)
    if "shared" in params:
        out = out + mlp(params["shared"], xt, "swiglu").astype(jnp.float32)
    return out.astype(x.dtype).reshape(B, S, D), sizes[:E]
