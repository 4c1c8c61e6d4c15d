"""Packed variable-length execution path (ISSUE 2).

Three layers of evidence that packing kills padding waste without
touching the math:

  * kernel parity — the segment-aware Pallas flash attention equals the
    block-diagonal masked reference in interpret mode across mask modes,
    uneven segment lengths and 1..8 segments (atol 1e-4, fp32);
  * packing correctness — flatten_group's labels/mask/positions never
    leak across segment boundaries, and each packed segment reproduces
    the same attention output as running that sequence alone;
  * executor invariants — packed vs per-sequence execution produces the
    SAME loss/gradients, with exe-miss count O(#buckets) (not
    O(#n_seqs)) and padding efficiency >= 0.85 on a heterogeneous
    RaggedBatch.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.group_pool import (GroupPool, geometric_bucket,
                                   make_bucket_fn, multiple_bucket,
                                   pow2_bucket)
from repro.core.packing import flatten_group, packing_efficiency
from repro.kernels.flash_attention import flash_attention_packed_flat
from repro.kernels.ops import flash_attention_packed
from repro.kernels.ref import flash_attention_packed_ref

KEY = jax.random.PRNGKey(0)

SEGMENT_SETS = [
    [64],                                # 1 segment
    [37, 27],                            # 2, uneven
    [5, 60, 3],                          # 3, very uneven
    [17, 1, 29, 13],                     # 4, incl. length-1
    [9, 9, 9, 9, 9, 9, 9, 9],            # 8 equal
    [31, 2, 19, 7, 11, 23, 3, 24],       # 8 uneven
]


def _packed_inputs(lens, BH=2, D=32, pad_to=None):
    total = sum(lens)
    S = pad_to or total
    seg = np.full(S, -1, np.int32)
    off = 0
    for i, L in enumerate(lens):
        seg[off:off + L] = i
        off += L
    q = jax.random.normal(KEY, (BH, S, D))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (BH, S, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (BH, S, D))
    return q, k, v, jnp.asarray(seg)


# ---------------------------------------------------------- kernel parity
@pytest.mark.parametrize("mode,window", [("causal", None), ("full", None),
                                         ("sliding", 8)])
@pytest.mark.parametrize("lens", SEGMENT_SETS,
                         ids=[f"{len(s)}seg" + ("-uneven" if len(set(s)) > 1
                                                else "")
                              for s in SEGMENT_SETS])
def test_packed_kernel_matches_blockdiag_ref(mode, window, lens):
    # tail padding: pad the packed buffer past the last segment
    q, k, v, seg = _packed_inputs(lens, pad_to=sum(lens) + 13)
    out = flash_attention_packed_flat(q, k, v, seg, mode=mode,
                                      window=window, block_q=32,
                                      block_k=32)
    ref = flash_attention_packed_ref(q, k, v, seg, mode=mode,
                                     window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_packed_kernel_padding_rows_are_zero():
    q, k, v, seg = _packed_inputs([20, 12], pad_to=64)
    out = flash_attention_packed_flat(q, k, v, seg, mode="causal",
                                      block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out[:, 32:]), 0.0, atol=0.0)


def test_packed_segments_equal_sequences_run_alone():
    """Each packed segment must reproduce the sequence run on its own —
    packing changes layout, never attention results."""
    from repro.kernels.ref import flash_attention_ref
    lens = [24, 40, 9]
    q, k, v, seg = _packed_inputs(lens, pad_to=96)
    out = flash_attention_packed_flat(q, k, v, seg, mode="causal",
                                      block_q=32, block_k=32)
    off = 0
    for L in lens:
        alone = flash_attention_ref(q[:, off:off + L], k[:, off:off + L],
                                    v[:, off:off + L], mode="causal")
        np.testing.assert_allclose(np.asarray(out[:, off:off + L]),
                                   np.asarray(alone), atol=1e-4,
                                   rtol=1e-4)
        off += L


def test_packed_ops_wrapper_gqa():
    """[B,S,H,D] wrapper with GQA expansion + per-row segment tables."""
    B, S, H, Hkv, D = 2, 64, 4, 2, 16
    q = jax.random.normal(KEY, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, Hkv, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, Hkv, D))
    seg = np.stack([
        np.repeat(np.arange(4), 16),          # row 0: 4x16 segments
        np.r_[np.zeros(50, int), -np.ones(14, int)],  # row 1: 1 + pad
    ]).astype(np.int32)
    out = flash_attention_packed(q, k, v, jnp.asarray(seg), mode="causal")
    ref = flash_attention_packed(q, k, v, jnp.asarray(seg), mode="causal",
                                 ref=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


# ------------------------------------------------- chunked core + grads
def test_packed_chunked_forward_and_grads():
    """The differentiable (custom-VJP) chunked path used by the
    executor: packed forward and gradients equal the block-diagonal
    reference."""
    from repro.models.attention import attn_chunked, attn_reference
    lens = [23, 41, 9]
    B, H, Hkv, D = 1, 4, 2, 16
    S = 96
    seg = np.full(S, -1, np.int32)
    off = 0
    for i, L in enumerate(lens):
        seg[off:off + L] = i
        off += L
    segj = jnp.asarray(seg)[None]
    q = jax.random.normal(KEY, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, Hkv, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, Hkv, D))

    out = attn_chunked(q, k, v, mode="causal", chunk=32, segment_ids=segj)
    ref = attn_reference(q, k, v, mode="causal", segment_ids=segj)
    valid = off
    np.testing.assert_allclose(np.asarray(out[:, :valid]),
                               np.asarray(ref[:, :valid]),
                               atol=2e-5, rtol=2e-5)

    g = jax.grad(lambda q, k, v: (attn_chunked(
        q, k, v, mode="causal", chunk=32,
        segment_ids=segj)[:, :valid] ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    gr = jax.grad(lambda q, k, v: (attn_reference(
        q, k, v, mode="causal",
        segment_ids=segj)[:, :valid] ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


# ------------------------------------------------------- flatten_group
def test_flatten_group_format():
    seqs = [np.arange(5, dtype=np.int32) + 1,
            np.arange(3, dtype=np.int32) + 100,
            np.array([7], dtype=np.int32)]
    batch, cu = flatten_group(seqs, bucket=16)
    assert list(cu) == [0, 5, 8, 9]
    t = batch["tokens"][0]
    np.testing.assert_array_equal(t[:5], [1, 2, 3, 4, 5])
    np.testing.assert_array_equal(t[5:8], [100, 101, 102])
    assert t[8] == 7 and (t[9:] == 0).all()
    # labels: next token WITHIN each segment; boundary + tail masked
    lab, m = batch["labels"][0], batch["mask"][0]
    np.testing.assert_array_equal(lab[:4], [2, 3, 4, 5])
    assert m[4] == 0.0          # last token of segment 0: no label
    np.testing.assert_array_equal(lab[5:7], [101, 102])
    assert m[7] == 0.0 and m[8] == 0.0      # len-1 segment: nothing
    assert m.sum() == (5 - 1) + (3 - 1) + 0
    # positions reset per segment
    pos = batch["positions"][0]
    np.testing.assert_array_equal(pos[:9], [0, 1, 2, 3, 4, 0, 1, 2, 0])
    # segment table with -1 tail
    np.testing.assert_array_equal(batch["segment_ids"][0][:9],
                                  [0, 0, 0, 0, 0, 1, 1, 1, 2])
    assert (batch["segment_ids"][0][9:] == -1).all()
    assert packing_efficiency(cu, 16) == pytest.approx(9 / 16)


def test_flatten_group_overflow_raises():
    with pytest.raises(ValueError):
        flatten_group([np.zeros(10, np.int32)], bucket=8)


# ------------------------------------------------------- bucket ladders
def test_bucket_ladders():
    assert pow2_bucket(100, 64) == 128
    assert pow2_bucket(129, 64) == 256
    # geometric 1.25x: monotone, >= n, 8-aligned, bounded waste (the
    # rungs don't coincide with pow2's, but overhead stays ~1.25x where
    # pow2's worst case is 2x)
    prev = 0
    for n in (65, 100, 200, 500, 1000, 5000):
        b = geometric_bucket(n, minimum=64)
        assert b >= n and b % 8 == 0 and b >= prev
        assert b <= n * 1.25 + 8
        prev = b
    assert multiple_bucket(100, 256) == 256
    assert multiple_bucket(257, 256) == 512
    assert multiple_bucket(512, 256) == 512
    assert make_bucket_fn("mult256")(300) == 512
    assert make_bucket_fn(lambda n: n)(123) == 123
    with pytest.raises(ValueError):
        make_bucket_fn("fib")


def test_group_pool_lru_eviction():
    pool = GroupPool(jax.devices() * 4, max_executables=2)
    _, miss = pool.executable_for("a", lambda: "A")
    assert miss
    pool.executable_for("b", lambda: "B")
    exe, miss = pool.executable_for("a", lambda: "A2")   # hit refreshes a
    assert exe == "A" and not miss
    pool.executable_for("c", lambda: "C")        # over cap: evicts b (LRU)
    assert pool.stats.exe_evictions == 1 and len(pool) == 2
    _, miss = pool.executable_for("b", lambda: "B2")     # b gone: re-miss
    assert miss                                          # (evicts a)
    exe, miss = pool.executable_for("c", lambda: "C2")   # c survived
    assert exe == "C" and not miss
    assert pool.stats.exe_misses == 4
    assert pool.stats.exe_hits == 2
    assert pool.stats.exe_evictions == 2


# ------------------------------------------------------ executor level
def _demo(cfg):
    from repro.core import CostModel, analytic_coeffs
    coeffs = dataclasses.replace(
        analytic_coeffs(hidden=cfg.d_model, n_layers=cfg.n_layers,
                        n_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
                        ffn=cfg.d_ff, vocab=cfg.vocab),
        m_ms=0.0, m_token=1.0)
    return CostModel(coeffs)


def test_executor_packed_kills_exe_explosion_and_padding():
    """The acceptance criteria of the issue, on ONE host device:

      * packed and per-sequence paths produce the same loss/grads;
      * packed exe-miss count is O(#buckets): one executable per
        distinct (degree, packed bucket), with n_seqs gone — at least
        2x fewer compilations than the per-sequence path;
      * padding efficiency >= 0.85 on a heterogeneous RaggedBatch
        (mult256 ladder), and strictly better than per-sequence pow2.
    """
    from repro.configs import get_config
    from repro.core import DHPScheduler
    from repro.core.executor import DHPExecutor
    from repro.data.pipeline import HeterogeneousLoader
    from repro.models.model import init_params

    cfg = get_config("internvl3-2b").reduced().with_(family="dense",
                                                     vlm=None)
    params = init_params(jax.random.PRNGKey(0), cfg)
    loader = HeterogeneousLoader("openvid", 24, cfg.vocab, seed=5,
                                 max_tokens=700, tokens_per_frame=16)
    data = next(iter(loader))
    plan = DHPScheduler(_demo(cfg), 1, mem_budget=1200.0).schedule(
        data.infos)
    n_groups = plan.n_groups
    assert n_groups >= 4      # heterogeneous enough to be interesting

    pool_p = GroupPool(jax.devices(), bucket_fn="mult256")
    pool_u = GroupPool(jax.devices(), bucket_fn="pow2")
    ex_p = DHPExecutor(cfg, pool=pool_p, packed=True)
    ex_u = DHPExecutor(cfg, pool=pool_u, packed=False)
    l_p, g_p = ex_p.run_plan(params, plan, data)
    l_u, g_u = ex_u.run_plan(params, plan, data)

    # same math
    assert abs(float(l_p) - float(l_u)) < 2e-5
    err = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
              for a, b in zip(jax.tree.leaves(g_p), jax.tree.leaves(g_u)))
    assert err < 1e-4, err

    # executable space: one exe per distinct (degree, packed bucket)
    packed_keys = set()
    for mb in plan.micro_batches:
        for g in mb.groups:
            total = sum(len(data.by_id(i)) for i in g.seq_ids)
            b = pool_p.bucket(total)
            b += (-b) % g.degree
            packed_keys.add((g.degree, b))
    assert pool_p.stats.exe_misses == len(packed_keys)
    assert pool_p.stats.exe_misses <= n_groups
    # n_seqs is gone: the per-sequence path compiles >= 2x more
    assert pool_u.stats.exe_misses >= 2 * pool_p.stats.exe_misses, (
        pool_u.stats, pool_p.stats)

    # padding: >= 0.85 packed (mult256), and better than per-seq pow2
    eff_p = ex_p.last_run_stats["padding_efficiency"]
    eff_u = ex_u.last_run_stats["padding_efficiency"]
    assert eff_p >= 0.85, ex_p.last_run_stats
    assert eff_p > eff_u, (eff_p, eff_u)
    # >= 30% reduction of padded-token overhead (overhead = padded-real)
    over_p = ex_p.last_run_stats["padded_tokens"] - \
        ex_p.last_run_stats["real_tokens"]
    over_u = ex_u.last_run_stats["padded_tokens"] - \
        ex_u.last_run_stats["real_tokens"]
    assert over_p <= 0.7 * over_u, (over_p, over_u)

    # warm pool: re-running compiles nothing, timing records say so
    timings = []
    ex_p.run_plan(params, plan, data, timings=timings)
    assert ex_p.last_run_stats["exe_misses"] == 0
    assert all(not t["compiled"] for t in timings)
    assert all(0 < t["padding_efficiency"] <= 1 for t in timings)
    assert {"real_tokens", "padded_tokens"} <= set(timings[0])


def test_executor_packed_rejects_stateful_families():
    from repro.configs import get_config
    from repro.core.executor import DHPExecutor
    cfg = get_config("mamba2-370m").reduced()
    with pytest.raises(ValueError):
        DHPExecutor(cfg, packed=True)
    ex = DHPExecutor(cfg)          # default: packed auto-disables
    assert not ex.packed


def test_ring_packed_segments(subproc):
    """Segment-aware ring CP: a packed buffer sharded over cp=3 must
    match the single-device block-diagonal reference — the segment
    table travels with each ppermute hop."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.parallel.ring_attention import ring_attention
from repro.models.attention import attn_reference

devs = jax.devices()
mesh = Mesh(np.array(devs[:3]), ("cp",))
B,H,Hkv,Dh = 1, 4, 2, 16
lens = [25, 40, 14, 17]         # 96 tokens = 3 shards x 32
S = 96
seg = np.full(S, -1, np.int32); pos = np.zeros(S, np.int32); off = 0
for i, L in enumerate(lens):
    seg[off:off+L] = i; pos[off:off+L] = np.arange(L); off += L
key = jax.random.PRNGKey(0)
q = jax.random.normal(key,(B,S,H,Dh))
k = jax.random.normal(jax.random.fold_in(key,1),(B,S,Hkv,Dh))
v = jax.random.normal(jax.random.fold_in(key,2),(B,S,Hkv,Dh))
posj = jnp.asarray(pos)[None]
segj = jnp.asarray(seg)[None]
fm = jax.shard_map(
    lambda q,k,v,p,s: ring_attention(q,k,v,p,axis_name="cp",q_seg=s),
    mesh=mesh, in_specs=(P(None,"cp"),)*5, out_specs=P(None,"cp"))
out = fm(q,k,v,posj,segj)
ref = attn_reference(q,k,v,mode="causal",segment_ids=segj)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           atol=3e-5, rtol=3e-5)
# grads flow through the segment-aware ring too
g = jax.grad(lambda q,k,v: (fm(q,k,v,posj,segj)**2).sum(),
             argnums=(0,1,2))(q,k,v)
gr = jax.grad(lambda q,k,v: (attn_reference(
    q,k,v,mode="causal",segment_ids=segj)**2).sum(),
             argnums=(0,1,2))(q,k,v)
for a,b in zip(g,gr):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=5e-4, rtol=5e-4)
print("ring packed ok")
""", n_devices=3)


def test_executor_packed_multidevice_cp(subproc):
    """Full packed execution with CP degree > 1 on 8 host devices:
    packed-vs-per-sequence gradient equivalence must survive sharding
    the packed buffer over the cp axis."""
    subproc("""
import dataclasses, jax, numpy as np
from repro.configs import get_config
from repro.core import CostModel, DHPScheduler, analytic_coeffs
from repro.core.executor import DHPExecutor
from repro.data.pipeline import HeterogeneousLoader
from repro.models.model import init_params

cfg = get_config("internvl3-2b").reduced().with_(family="dense", vlm=None)
params = init_params(jax.random.PRNGKey(0), cfg)
loader = HeterogeneousLoader("openvid", 12, cfg.vocab, seed=1,
                             max_tokens=512, tokens_per_frame=16)
data = next(iter(loader))
coeffs = dataclasses.replace(
    analytic_coeffs(hidden=cfg.d_model, n_layers=cfg.n_layers,
                    n_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
                    ffn=cfg.d_ff, vocab=cfg.vocab), m_ms=0.0, m_token=1.0)
plan = DHPScheduler(CostModel(coeffs), 8, mem_budget=900.0).schedule(
    data.infos)
assert any(g.degree > 1 for mb in plan.micro_batches for g in mb.groups)
ex_p = DHPExecutor(cfg, packed=True)
ex_u = DHPExecutor(cfg, packed=False)
l_p, g_p = ex_p.run_plan(params, plan, data)
l_u, g_u = ex_u.run_plan(params, plan, data)
assert abs(float(l_p) - float(l_u)) < 2e-5, (float(l_p), float(l_u))
err = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
          for a, b in zip(jax.tree.leaves(g_p), jax.tree.leaves(g_u)))
assert err < 1e-4, err
assert ex_p.last_run_stats["padding_efficiency"] >= \
    ex_u.last_run_stats["padding_efficiency"]
print("packed cp ok", err)
""", n_devices=8)
