"""Latent attention (MLA) and the dropless expert layer over a held share
of the experts, against the benchmark's plain float32 reference
(`benchmarks/chip/models/mla_moe.py`, loaded by path as the benchmark
loads it), on the reduced kimi-vl-a3b: 1 dense + 2 expert layers, 4 of
8 routed experts held. Also: the dense GQA path is unchanged (its grad
step's lowered program and its planner coefficients equal golden values
recorded before MLA and the expert layer were added)."""
import dataclasses
import hashlib
import importlib.util
import json
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import moe as moe_mod
from repro.models.attention import init_mla, mla_attention

CHIP = Path(__file__).resolve().parent.parent / "benchmarks" / "chip"
if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))
F32 = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _module(CHIP / "models" / "mla_moe.py", "bench_model_mla_moe")
REDUCED = get_config("kimi-vl-a3b").reduced()


def bench_cfg(mc, **kw):
    """The benchmark configuration (config.json's keys) of a ModelConfig."""
    m, e = mc.mla, mc.moe
    return {"arch": mc.arch_id, "num_hidden_layers": mc.n_layers,
            "first_k_dense_replace": e.first_dense,
            "hidden_size": mc.d_model, "num_attention_heads": mc.n_heads,
            "kv_lora_rank": m.kv_lora_rank,
            "qk_nope_head_dim": m.qk_nope_head_dim,
            "qk_rope_head_dim": m.qk_rope_head_dim,
            "v_head_dim": m.v_head_dim, "intermediate_size": mc.d_ff,
            "moe_intermediate_size": e.expert_ff,
            "n_shared_experts": e.n_shared, "n_routed_experts": e.n_experts,
            "router_experts": e.n_routed, "expert_offset": e.expert_offset,
            "num_experts_per_tok": e.top_k,
            "routed_scaling_factor": e.route_scale,
            "norm_topk_prob": True, "scoring_func": e.scoring,
            "rms_norm_eps": mc.norm_eps,
            "rope_theta": mc.rope_theta, "vocab": mc.vocab,
            "hidden_act": "silu", "tie_word_embeddings": False,
            "param_dtype": "float32", "remat": False, **kw}


def packed_row(lens, blocks, S):
    """The reference's row fields for sequences of `lens` packed into S
    tokens; `blocks` lists (sequence, start, length) bidirectional
    blocks."""
    seg = np.full(S, -1, np.int32)
    pos = np.zeros(S, np.int32)
    block = np.full(S, -1, np.int32)
    off, starts = 0, []
    for i, n in enumerate(lens):
        seg[off:off + n], pos[off:off + n] = i, np.arange(n)
        starts.append(off)
        off += n
    for b, (i, s, n) in enumerate(blocks):
        block[starts[i] + s:starts[i] + s + n] = b
    return {"seg": seg, "pos": pos, "block": block}


LENS, BLOCKS, S = [25, 40, 14, 17], [(1, 4, 12), (3, 2, 9)], 96


def _mla_params(key):
    return init_mla(key, REDUCED.d_model, REDUCED.n_heads, REDUCED.mla,
                    jnp.float32)


def _ref_mla(p, x, row):
    cfg = bench_cfg(REDUCED)
    return REF._mla(x, p, row, cfg, REF._sizes(cfg), F32)


def test_mla_through_the_ring_at_degree_one():
    """MLA inside shard_map on a one-device ring, packed segments and
    bidirectional blocks: the reference's attention, forward and
    gradient."""
    from jax.sharding import Mesh, PartitionSpec as P

    row = packed_row(LENS, BLOCKS, S)
    key = jax.random.PRNGKey(0)
    p = _mla_params(key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, S, REDUCED.d_model))
    mesh = Mesh(np.array(jax.devices()[:1]), ("cp",))
    ring = jax.shard_map(
        lambda p, x, pos, seg, span: mla_attention(
            p, x, n_heads=REDUCED.n_heads, mla=REDUCED.mla,
            rope_theta=REDUCED.rope_theta, positions=pos, cp_axis="cp",
            segment_ids=seg, span_ids=span),
        mesh=mesh, in_specs=(P(),) + (P(None, "cp"),) * 4,
        out_specs=P(None, "cp"))
    tables = [jnp.asarray(row[k])[None] for k in ("pos", "seg", "block")]
    jrow = {k: jnp.asarray(v) for k, v in row.items()}
    real = row["seg"] >= 0
    w = jax.random.normal(jax.random.fold_in(key, 2), (1, S, REDUCED.d_model)
                          ) * real[:, None]
    out, g = jax.jit(lambda p: (ring(p, x, *tables), jax.grad(
        lambda p: jnp.sum(ring(p, x, *tables) * w))(p)))(p)
    ref, gr = jax.jit(lambda p: (_ref_mla(p, x[0], jrow), jax.grad(
        lambda p: jnp.sum(_ref_mla(p, x[0], jrow) * w[0]))(p)))(p)
    np.testing.assert_allclose(np.asarray(out[0])[real],
                               np.asarray(ref)[real], atol=2e-5, rtol=2e-5)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_mla_ring_of_two_and_three_and_the_grad_step(subproc):
    """MLA through rings of 2 and 3 CPU devices (packed segments,
    bidirectional blocks, positions and both tables riding the hops)
    against the reference; and the executor's packed grad step of the
    reduced model at degree 2 equals its degree-1 step (loss, every
    gradient, the routed-pair table)."""
    out = subproc(f"""
import sys, importlib.util
from functools import partial
sys.path.insert(0, {str(CHIP)!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
sys.path.insert(0, {str(Path(__file__).resolve().parent)!r})
import test_mla_moe as t
from repro.core.executor import DHPExecutor
from repro.core.group_pool import GroupPool
from repro.core.packing import flatten_group
from repro.models.attention import mla_attention
from repro.models.model import init_params
row = t.packed_row(t.LENS, t.BLOCKS, t.S)
key = jax.random.PRNGKey(0)
p = t._mla_params(key)
x = jax.random.normal(jax.random.fold_in(key, 1), (1, t.S, t.REDUCED.d_model))
ref = jax.jit(t._ref_mla)(p, x[0], {{k: jnp.asarray(v) for k, v in row.items()}})
real = row["seg"] >= 0
tables = [jnp.asarray(row[k])[None] for k in ("pos", "seg", "block")]
for d in (2, 3):
    mesh = Mesh(np.array(jax.devices()[:d]), ("cp",))
    ring = jax.shard_map(
        lambda p, x, pos, seg, span: mla_attention(
            p, x, n_heads=t.REDUCED.n_heads, mla=t.REDUCED.mla,
            rope_theta=t.REDUCED.rope_theta, positions=pos, cp_axis="cp",
            segment_ids=seg, span_ids=span),
        mesh=mesh, in_specs=(P(),) + (P(None, "cp"),) * 4,
        out_specs=P(None, "cp"))
    out = jax.jit(ring)(p, x, *tables)
    np.testing.assert_allclose(np.asarray(out[0])[real],
                               np.asarray(ref)[real], atol=2e-5, rtol=2e-5)
cfg = t.REDUCED
ex = DHPExecutor(cfg, pool=GroupPool(jax.devices()[:2]))
params = init_params(jax.random.PRNGKey(1), cfg)
rng = np.random.default_rng(0)
seqs = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in (50, 31, 47)]
outs = []
for d in (1, 2):
    batch, _ = flatten_group(seqs, 128)
    step, _, _ = ex._packed_grad_fn(0, d, 128, with_spans=False)
    outs.append(jax.device_get(step(params, {{k: jnp.asarray(v) for k, v in batch.items()}})))
(l1, g1, a1), (l2, g2, a2) = outs
np.testing.assert_allclose(l2, l1, rtol=1e-5)
for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
    np.testing.assert_allclose(b, a, atol=2e-6, rtol=2e-4)
np.testing.assert_array_equal(a2["routes"], a1["routes"])
print("pairs", int(a1["routes"].sum()))
""", n_devices=3)
    pairs = int(out.split("pairs")[-1])
    # 128 real tokens x 2 slots x 2 expert layers, about half held
    assert 0 < pairs <= 128 * 2 * 2


def _layer_params(key, moe):
    return moe_mod.init_moe_dropless(key, REDUCED.d_model, moe, jnp.float32)


def _ref_experts(p, x, moe):
    cfg = bench_cfg(REDUCED.with_(moe=moe))
    return REF._experts(x, p, cfg, REF._sizes(cfg), F32)


def test_shares_add_up_to_the_uncut_layer():
    """Each of the two shares (experts 0-3 and 4-7 of 8) computes its
    held experts' part; the parts, with the shared experts counted once,
    add up to the layer that holds all 8, and to the reference's."""
    whole = dataclasses.replace(REDUCED.moe, n_experts=8, expert_offset=0)
    key = jax.random.PRNGKey(3)
    p = _layer_params(key, whole)
    p["router_bias"] = 0.02 * jax.random.normal(jax.random.fold_in(key, 9),
                                                (8,))
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 24, REDUCED.d_model))
    layer = jax.jit(moe_mod.moe_dropless, static_argnums=2)
    uncut, uncut_pairs = layer(p, x, whole)
    parts, pairs = [], []
    for off in (0, 4):
        share = dataclasses.replace(whole, n_experts=4, expert_offset=off)
        held = {**p, **{k: p[k][off:off + 4] for k in ("gate", "up", "down")}}
        out, n = layer(held, x, share)
        parts.append(out)
        pairs.append(n)
    shared = moe_mod.mlp(p["shared"], x, "swiglu")
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] - shared),
                               np.asarray(uncut), atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.concatenate(pairs), uncut_pairs)
    assert int(uncut_pairs.sum()) == 2 * 24 * whole.top_k
    ref = jax.jit(jax.vmap(lambda xb: _ref_experts(p, xb, whole)))(x)
    np.testing.assert_allclose(np.asarray(uncut), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_no_drops_under_skew():
    """A router bias that sends every token to the same experts (all
    held here): each held expert computes every token, as the dense
    reference does; a capacity layer would drop most of them."""
    moe = REDUCED.moe
    key = jax.random.PRNGKey(4)
    p = _layer_params(key, moe)
    p["router_bias"] = jnp.zeros((moe.n_routed,)).at[:moe.top_k].set(10.0)
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, 64, REDUCED.d_model))
    out, pairs = jax.jit(moe_mod.moe_dropless, static_argnums=2)(p, x, moe)
    np.testing.assert_array_equal(
        pairs, [64] * moe.top_k + [0] * (moe.n_experts - moe.top_k))
    ref = jax.jit(lambda x: _ref_experts(p, x, moe))(x[0])
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_engine_train_step_matches_row_loss():
    """One `Engine.train` step of the reduced model (float32) on a
    seeded OpenVid-like step: its loss and each leaf's gradient norm
    (from Adam's first moment) match the reference's `row_loss`; the
    run's routed-pair counts reach `last_run_stats` and the tracer."""
    import harness
    import model_init
    import reference
    import traffic
    from repro.api import ClusterSpec, Engine
    from repro.core.group_pool import multiple_bucket
    from repro.training.optimizer import AdamW
    from repro.training.train_step import TrainState

    cfg = bench_cfg(REDUCED, remat=True)
    mix = traffic.load(CHIP / "tests" / "data" / "tiny" / "tinyvid.json")
    key = traffic.weight_key(11)
    seqs, toks = traffic.step(mix, cfg["vocab"], 11, 0)
    engine = Engine(REF.program_config(cfg), ClusterSpec(
        devices=jax.devices()[:1], mem_budget=float(mix["rank_budget_tokens"]),
        bucketing=partial(multiple_bucket, multiple=mix["bucket_multiple"])),
        strategy="dhp", optimizer=AdamW(**mix["optimizer"]))
    params = model_init.make_params(REF, cfg, key)
    engine.state = TrainState(params, engine.optimizer.init(params))
    from repro.obs import Tracer
    tracer = Tracer()
    hist = engine.train([harness.ragged(seqs, toks, "vision")], steps=1,
                        trace=tracer)
    m = jax.tree.leaves(engine.state.opt.m)
    grads = [float(jnp.linalg.norm(a)) / (1 - mix["optimizer"]["b1"])
             for a in m]
    stats = engine.executor.last_run_stats
    engine.close()
    ref = reference.run(REF, cfg, mix, key, [(seqs, toks)])
    assert hist[0].loss == pytest.approx(ref["losses"][0], rel=1e-5)
    np.testing.assert_allclose(grads, ref["grad_norms"], rtol=2e-4,
                               atol=1e-6)
    real = sum(traffic.seq_len(s) for s in seqs)
    assert 0 < stats["moe_routed_pairs"] <= real * 2 * 2
    assert 0 < stats["moe_max_expert_pairs"] <= real
    counters = [e["args"] for e in tracer.to_json()["traceEvents"]
                if e.get("ph") == "C" and e["name"] == "moe.routes"]
    assert counters == [{"routed_pairs": stats["moe_routed_pairs"],
                         "max_expert_pairs": stats["moe_max_expert_pairs"]}]


def test_reduced_holds_a_share():
    assert (REDUCED.n_layers, REDUCED.moe.first_dense) == (3, 1)
    assert (REDUCED.moe.n_experts, REDUCED.moe.n_routed) == (4, 8)
    assert REDUCED.moe.dropless and REDUCED.mla is not None


# --------------------------------------------------------------------------
# the dense path is unchanged
# --------------------------------------------------------------------------
#: sha256 of the lowered `fwd_bwd` of the reduced internvl3-2b packed grad
#: step (256-token bucket, one device), causal and span-bearing: recorded
#: after the one-hot gold logit, whose equality with the gather it
#: replaced `tests/test_token_nll.py` checks. The planner coefficients of
#: internvl3-2b: recorded before MLA and the dropless expert layer were
#: added
DENSE_GRAD_STEP_SHA256 = {
    False: "8da4a1eec685cfbc0244091a07e672f7f5e150ffd9c99b9050479589f7823ce0",
    True: "6d3d99edceba094173ff510404d1b741acd703af380cbdcf41c9fd724a58cc3b",
}
DENSE_COEFFS = {
    28: {"a1": 2.9108629441624364e-09, "a2": 0.00010444446538071066,
         "b1": 0.002, "a3": 1.024e-06, "b2": 0.0001, "m_token": 1.0,
         "m_ms": 0.0},
    4: {"a1": 4.1583756345177664e-10, "a2": 2.8436012182741117e-05,
        "b1": 0.002, "a3": 1.024e-06, "b2": 0.0001, "m_token": 1.0,
        "m_ms": 0.0},
}


@pytest.mark.parametrize("with_spans", [False, True],
                         ids=["causal", "spans"])
def test_dense_grad_step_program_is_unchanged(with_spans):
    from repro.core.executor import DHPExecutor
    from repro.models.model import init_params

    cfg = get_config("internvl3-2b").reduced().with_(family="dense",
                                                     vlm=None)
    ex = DHPExecutor(cfg, devices=jax.devices()[:1])
    params = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    step, _, _ = ex._packed_grad_fn(0, 1, 256, with_spans=with_spans)
    keys = ["tokens", "labels", "mask", "positions"]
    if with_spans:
        keys += ["modality_ids", "loss_mask", "modality_classes"]
    floats = ("mask", "loss_mask")
    batch = {k: jax.ShapeDtypeStruct(
        (1, 256), np.float32 if k in floats else np.int32)
        for k in keys + ["segment_ids"]}
    text = step.lower(params, batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        DENSE_GRAD_STEP_SHA256[with_spans]


@pytest.mark.parametrize("layers", [28, 4])
def test_dense_planner_coefficients_are_unchanged(layers):
    from repro.api.engine import demo_cost_model

    cfg = get_config("internvl3-2b").with_(n_layers=layers)
    if layers != 28:
        cfg = cfg.with_(family="dense", vlm=None)
    got = dataclasses.asdict(demo_cost_model(cfg).coeffs)
    assert json.dumps(got, sort_keys=True) == json.dumps(
        DENSE_COEFFS[layers], sort_keys=True)


def test_planner_costs_latent_attention_and_active_experts():
    """kimi-vl-a3b at the benchmark's cut: MLA's 16 x (192 + 128) score
    width and K/V hop bytes, the dense layer and the active expert width
    (shared + router + 6 x 8/64 routed) in the linear FLOPs."""
    from repro.core.cost_model import model_costs

    base = get_config("kimi-vl-a3b")
    cfg = base.with_(n_layers=6, vocab=20480, moe=dataclasses.replace(
        base.moe, n_experts=8, router_experts=64))
    c = model_costs(cfg)
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    active = 3 * 2048 * 2816 + 2048 * 64 + 0.75 * 3 * 2048 * 1408
    lin = 6 * attn + 3 * 2048 * 11264 + 5 * active
    assert c["lin_flops_per_tok"] == pytest.approx(6 * lin + 6 * 2048 * 20480)
    assert c["attn_flops_per_tok2"] == 3 * 2 * 16 * 320 * 6 * 0.5
    assert c["kv_hop_bytes"] == 16 * 320 * 2
    # the matmuls' parameters: all but the norm scales and router biases
    assert c["params"] == pytest.approx(668890432, rel=1e-4)
    assert model_costs(get_config("internvl3-2b")) == {}
