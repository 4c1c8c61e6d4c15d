"""First-class multimodal sequences (ISSUE 5).

Four layers of evidence that modality structure is now a real input,
not a derived scalar:

  * mask correctness — span-masked packed attention (Pallas kernel +
    block-diagonal reference + the differentiable chunked path) matches
    an independently constructed dense-mask oracle, forward and grad,
    across 1..8 segments with interleaved vision spans;
  * cost derivation — the span→eta derivation reproduces the scalar
    Eq. 8 path bit-for-bit when spans are synthesized from a target
    eta, and two sequences of EQUAL length but different span layouts
    get different costs/degrees;
  * plan IR — span-bearing plans JSON round-trip bit-identically (hash
    verified) for every registered planner, and the PlanCache keys
    modality mixes apart;
  * serving — requests carry spans, the scheduler never splits a
    bidirectional block across prefill chunks, and span-aware chunked
    prefill is invariant to the chunking.
"""
import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import (CostModel, ExecutionPlan, MMSequence,
                        ModalitySpan, SeqInfo, analytic_coeffs,
                        evaluate_degrees, sample_mm_batch, slice_spans,
                        spans_eta, synthesize_spans)
from repro.core.packing import flatten_group
from repro.kernels.flash_attention import flash_attention_packed_flat
from repro.kernels.ref import flash_attention_packed_ref
from repro.models.attention import attn_chunked, attn_reference

KEY = jax.random.PRNGKey(0)
NEG_INF = -1e30

CM = CostModel(dataclasses.replace(
    analytic_coeffs(hidden=1024, n_layers=8, n_heads=8, kv_heads=4,
                    ffn=4096, vocab=32000),
    m_ms=0.0, m_token=1.0))


# ------------------------------------------------------------ helpers
def _interleaved_layout(lens, vis_frac=0.5, frame=8):
    """seg/span tables + per-seq spans for packed buffers: each segment
    gets bidirectional vision frames of `frame` tokens interleaved with
    causal text, ~vis_frac of its tokens vision."""
    S = sum(lens)
    seg = np.full(S, -1, np.int32)
    span = np.full(S, -1, np.int32)
    spans_per_seq = []
    off, sid = 0, 0
    for i, L in enumerate(lens):
        seg[off:off + L] = i
        spans = []
        p = 0
        vis_left = int(L * vis_frac)
        while p < L:
            t = min(max(1, frame // 2), L - p)       # text block
            spans.append(ModalitySpan("text", p, t))
            p += t
            if vis_left > 0 and p < L:
                f = min(frame, vis_left, L - p)
                spans.append(ModalitySpan("vision", p, f,
                                          "bidirectional"))
                span[off + p:off + p + f] = sid
                sid += 1
                vis_left -= f
                p += f
        spans_per_seq.append(tuple(spans))
        off += L
    return seg, span, spans_per_seq


def _dense_oracle(q, k, v, seg, span):
    """Independent dense-mask oracle in float64 numpy: causal within a
    segment, OR same-bidirectional-block, rows without keys -> 0."""
    BH, S, D = q.shape
    s = np.einsum("bqd,bkd->bqk", np.asarray(q, np.float64),
                  np.asarray(k, np.float64)) / math.sqrt(D)
    seg = np.asarray(seg)
    span = np.asarray(span)
    same = (seg[:, None] == seg[None, :]) & (seg[:, None] >= 0)
    ok = np.tril(np.ones((S, S), bool))
    ok |= (span[:, None] >= 0) & (span[:, None] == span[None, :])
    m = same & ok
    s = np.where(m[None], s, NEG_INF)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    o = np.einsum("bqk,bkd->bqd", p, np.asarray(v, np.float64))
    return np.where(m.any(-1)[None, :, None], o, 0.0)


SEGMENT_SETS = [
    [64],                                # 1 segment
    [37, 27],
    [5, 60, 3],
    [17, 9, 29, 13],
    [9, 9, 9, 9, 9, 9, 9, 9],            # 8 equal
    [31, 6, 19, 7, 11, 23, 5, 24],       # 8 uneven
]


# ---------------------------------------------------- kernel acceptance
@pytest.mark.parametrize("lens", SEGMENT_SETS,
                         ids=[f"{len(s)}seg" for s in SEGMENT_SETS])
def test_span_masked_packed_kernels_match_dense_oracle(lens):
    """Acceptance: Pallas packed kernel + block-diagonal reference with
    interleaved vision spans match the dense-mask oracle, atol 1e-4,
    including tail padding (exact zeros)."""
    seg, span, _ = _interleaved_layout(lens)
    S = sum(lens) + 11                    # tail padding
    segp = np.full(S, -1, np.int32)
    spanp = np.full(S, -1, np.int32)
    segp[:sum(lens)] = seg
    spanp[:sum(lens)] = span
    q = jax.random.normal(KEY, (3, S, 32))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (3, S, 32))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (3, S, 32))
    oracle = _dense_oracle(q, k, v, segp, spanp)
    out = flash_attention_packed_flat(
        q, k, v, jnp.asarray(segp), span_ids=jnp.asarray(spanp),
        block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), oracle,
                               atol=1e-4, rtol=1e-4)
    ref = flash_attention_packed_ref(q, k, v, jnp.asarray(segp),
                                     span_ids=jnp.asarray(spanp))
    np.testing.assert_allclose(np.asarray(ref), oracle,
                               atol=1e-4, rtol=1e-4)
    # the mixed mask is real: dropping the span table changes vision rows
    causal = flash_attention_packed_flat(
        q, k, v, jnp.asarray(segp), block_q=32, block_k=32)
    assert float(jnp.abs(out - causal).max()) > 1e-3


@pytest.mark.parametrize("lens", [[64], [37, 27], [17, 9, 29, 13]],
                         ids=["1seg", "2seg", "4seg"])
def test_span_masked_grads_match_dense_oracle(lens):
    """Acceptance: the differentiable (custom-VJP) chunked path used by
    the executor matches the dense-mask oracle forward AND grad with
    interleaved vision spans (valid region; padding rows are loss-masked
    by construction)."""
    seg, span, _ = _interleaved_layout(lens)
    valid = sum(lens)
    S = valid + 13
    segp = np.full(S, -1, np.int32)
    spanp = np.full(S, -1, np.int32)
    segp[:valid] = seg
    spanp[:valid] = span
    B, H, Hkv, D = 1, 4, 2, 16
    q = jax.random.normal(KEY, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, Hkv, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, Hkv, D))
    segj = jnp.asarray(segp)[None]
    spanj = jnp.asarray(spanp)[None]

    def dense(q, k, v):
        """dense-mask oracle, differentiable (GQA expanded)."""
        kf = jnp.repeat(k, H // Hkv, axis=2).astype(jnp.float32)
        vf = jnp.repeat(v, H // Hkv, axis=2).astype(jnp.float32)
        s = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                       kf.transpose(0, 1, 2, 3)) / math.sqrt(D)
        same = (segj[:, :, None] == segj[:, None, :]) \
            & (segj >= 0)[:, :, None]
        ok = jnp.tril(jnp.ones((S, S), bool))[None]
        ok = ok | ((spanj[:, :, None] >= 0)
                   & (spanj[:, :, None] == spanj[:, None, :]))
        m = same & ok
        s = jnp.where(m[:, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhst,bthd->bshd", p, vf)
        return jnp.where(m.any(-1)[:, :, None, None], o, 0.0)

    out = attn_chunked(q, k, v, mode="causal", chunk=32,
                       segment_ids=segj, span_ids=spanj)
    # q is [B,S,H,D]; dense expects the same layout via einsum over h
    den = dense(q.transpose(0, 1, 2, 3), k, v)
    np.testing.assert_allclose(np.asarray(out[:, :valid]),
                               np.asarray(den[:, :valid]),
                               atol=1e-4, rtol=1e-4)
    g = jax.grad(lambda a, b, c: (attn_chunked(
        a, b, c, mode="causal", chunk=32, segment_ids=segj,
        span_ids=spanj)[:, :valid] ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    gr = jax.grad(lambda a, b, c: (
        dense(a, b, c)[:, :valid] ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_attn_reference_span_equals_dense_oracle():
    seg, span, _ = _interleaved_layout([24, 40])
    S = 64
    B, H, Hkv, D = 2, 4, 2, 16
    q = jax.random.normal(KEY, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(KEY, 5), (B, S, Hkv, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 6), (B, S, Hkv, D))
    out = attn_reference(q, k, v, mode="causal",
                         segment_ids=jnp.asarray(seg)[None],
                         span_ids=jnp.asarray(span)[None])
    kf = jnp.repeat(k, 2, 2).transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vf = jnp.repeat(v, 2, 2).transpose(0, 2, 1, 3).reshape(B * H, S, D)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    oracle = _dense_oracle(qf, kf, vf, seg, span)
    got = np.asarray(out.transpose(0, 2, 1, 3).reshape(B * H, S, D))
    np.testing.assert_allclose(got, oracle, atol=1e-4, rtol=1e-4)


def test_ring_span_table_rides_hops(subproc):
    """Mixed-mask ring CP: the modality table travels with every
    ppermute hop (alongside positions + segment ids), so a packed
    span-bearing buffer sharded over cp=3 matches the single-device
    reference, forward and grad."""
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
seg = np.full(S, -1, np.int32); pos = np.zeros(S, np.int32)
span = np.full(S, -1, np.int32)
off = 0; sid = 0
for i, L in enumerate(lens):
    seg[off:off+L] = i; pos[off:off+L] = np.arange(L)
    # one vision block in the middle of each sequence (crosses shard
    # boundaries for the longer ones)
    a, b = off + L//4, off + 3*L//4
    span[a:b] = sid; sid += 1
    off += L
key = jax.random.PRNGKey(0)
q = jax.random.normal(key,(B,S,H,Dh))
k = jax.random.normal(jax.random.fold_in(key,1),(B,S,Hkv,Dh))
v = jax.random.normal(jax.random.fold_in(key,2),(B,S,Hkv,Dh))
posj = jnp.asarray(pos)[None]
segj = jnp.asarray(seg)[None]
spanj = jnp.asarray(span)[None]
fm = jax.shard_map(
    lambda q,k,v,p,s,sp: ring_attention(q,k,v,p,axis_name="cp",
                                        q_seg=s,q_span=sp),
    mesh=mesh, in_specs=(P(None,"cp"),)*6, out_specs=P(None,"cp"))
out = fm(q,k,v,posj,segj,spanj)
ref = attn_reference(q,k,v,mode="causal",segment_ids=segj,
                     span_ids=spanj)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           atol=3e-5, rtol=3e-5)
g = jax.grad(lambda q,k,v: (fm(q,k,v,posj,segj,spanj)**2).sum(),
             argnums=(0,1,2))(q,k,v)
gr = jax.grad(lambda q,k,v: (attn_reference(
    q,k,v,mode="causal",segment_ids=segj,span_ids=spanj)**2).sum(),
             argnums=(0,1,2))(q,k,v)
for a,b in zip(g,gr):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=5e-4, rtol=5e-4)
print("ring span ok")
""", n_devices=3)


# -------------------------------------------------------- eta derivation
def test_spans_eta_anchors():
    full = (ModalitySpan("vision", 0, 100, "bidirectional"),)
    assert spans_eta(full) == 1.0
    text = (ModalitySpan("text", 0, 100),)
    assert spans_eta(text) == 0.0
    # splitting a block lowers eta: structure matters, not just counts
    one = (ModalitySpan("vision", 0, 64, "bidirectional"),
           ModalitySpan("text", 64, 64),)
    two = (ModalitySpan("vision", 0, 32, "bidirectional"),
           ModalitySpan("text", 32, 32),
           ModalitySpan("vision", 64, 32, "bidirectional"),
           ModalitySpan("text", 96, 32),)
    assert spans_eta(one) > spans_eta(two) > 0.0


@settings(max_examples=60, deadline=None)
@given(st.integers(8, 4096), st.floats(0.0, 1.0), st.integers(1, 9))
def test_span_eta_matches_scalar_group_time(length, frac, degree):
    """Property (satellite): a span layout synthesized from a target
    eta reproduces the SCALAR cost path exactly — group_time equal
    within 1e-9 relative, across degrees."""
    v = int(round(math.sqrt(frac) * length))
    eta = v * v / float(length) ** 2          # representable target
    spans = synthesize_spans(length, eta)
    structural = SeqInfo(length=0, seq_id=0, spans=spans)
    scalar = SeqInfo(length=length, eta=eta, seq_id=0)
    assert structural.length == length
    assert structural.eta == pytest.approx(eta, abs=1e-15)
    t_structural = CM.group_time([structural], degree)
    t_scalar = CM.group_time([scalar], degree)
    assert t_structural == pytest.approx(t_scalar, rel=1e-9)


def test_mmsequence_seqinfo_view_and_validation():
    mm = MMSequence(spans=(ModalitySpan("text", 0, 10),
                           ModalitySpan("vision", 10, 20,
                                        "bidirectional")), seq_id=5)
    si = mm.seq_info
    assert si.length == mm.length == 30
    assert si.eta == mm.eta == pytest.approx(400 / 900)
    assert si.seq_id == 5 and si.spans == mm.spans
    assert mm.modality_tokens() == {"text": 10, "vision": 20}
    with pytest.raises(ValueError):        # gap in the tiling
        MMSequence(spans=(ModalitySpan("text", 0, 10),
                          ModalitySpan("vision", 12, 8)))
    with pytest.raises(ValueError):        # bogus attn kind
        ModalitySpan("vision", 0, 4, attn="fancy")
    # slicing re-bases and clips
    assert slice_spans(mm.spans, 5, 10) == (
        ModalitySpan("text", 0, 5), ModalitySpan("vision", 5, 5,
                                                 "bidirectional"))


def test_seqinfo_legacy_construction_unchanged():
    s = SeqInfo(2048, 0.7, 3)
    assert (s.length, s.eta, s.seq_id, s.spans) == (2048, 0.7, 3, None)
    assert s.attn_weight == pytest.approx(1.7 * 2048 ** 2)


# -------------------------------------------- planner cost sensitivity
def _layout_pair(length=16384):
    """Two sequences of EQUAL length whose span layouts differ: one
    monolithic vision block vs the same vision budget split into many
    frames. Derived eta (and hence Eq. 8 cost) must differ."""
    vis = length * 3 // 4
    mono = SeqInfo(length=0, seq_id=0, spans=(
        ModalitySpan("vision", 0, vis, "bidirectional"),
        ModalitySpan("text", vis, length - vis)))
    frames = []
    off = 0
    frame = vis // 16
    for _ in range(16):
        frames.append(ModalitySpan("vision", off, frame,
                                   "bidirectional"))
        off += frame
    frames.append(ModalitySpan("text", off, length - off))
    split = SeqInfo(length=0, seq_id=0, spans=tuple(frames))
    assert mono.length == split.length == length
    assert mono.eta > split.eta
    return mono, split


def test_mixed_modality_changes_evaluate_degrees_and_chosen_degrees():
    """Satellite: same length, different span layout -> different
    derived eta -> different evaluated cost AND different chosen CP
    degrees when the allocator splits one rank pool between them."""
    from repro.core import DHPScheduler
    mono, split = _layout_pair()
    ev_mono = evaluate_degrees([[mono]], [4], CM.group_time)
    ev_split = evaluate_degrees([[split]], [4], CM.group_time)
    assert ev_mono.makespan > ev_split.makespan
    # both sequences in ONE wave on 16 ranks: the min-makespan DP must
    # give the monolithic-vision (higher derived eta) sequence MORE
    # ranks than the frame-split one of identical length
    heavy = CostModel(dataclasses.replace(
        CM.coeffs, a1=CM.coeffs.a1 * 50))
    batch = [dataclasses.replace(mono, seq_id=0),
             dataclasses.replace(split, seq_id=1)]
    budget = mono.length * 0.6          # one atomic group per sequence
    plan = DHPScheduler(heavy, 16, budget, balance_packing=False,
                        serial_fallback=False).schedule(batch)
    degree = {i: g.degree for mb in plan.micro_batches
              for g in mb.groups for i in g.seq_ids}
    assert degree[0] > degree[1], degree


def test_oracle_plan_cost_sees_span_structure():
    """Satellite: the oracle's plan_cost (analytic fallback before any
    measurements land) prices span layouts apart for equal lengths."""
    from repro.api import get_strategy
    mono, split = _layout_pair()
    strat = get_strategy("oracle").bind(CM, 8, float(mono.length))
    plan = strat.plan([mono])
    assert strat.plan_cost(plan, [mono]) > strat.plan_cost(plan, [split])


def test_plan_cache_distinguishes_modality_mixes():
    from repro.core import PlanCache
    mono, split = _layout_pair(4096)
    cache = PlanCache()
    assert cache.key([mono]) != cache.key([split])
    # scalar SeqInfos keep the legacy key space (no span signature)
    a = SeqInfo(4096, 0.5, 0)
    b = SeqInfo(4096, 0.5, 1)
    assert cache.key([a]) == cache.key([b])


# ------------------------------------------------------------ plan IR
PLANNERS = ("static", "megatron", "deepspeed", "dhp", "dhp-faithful",
            "bruteforce")


def _mm_batch(seed, n=6):
    rng = np.random.default_rng(seed)
    return sample_mm_batch("openvid", n, rng, max_tokens=2000,
                           tokens_per_frame=32)


@pytest.mark.parametrize("name", PLANNERS)
def test_plan_ir_round_trips_spans_bit_identically(name):
    """Satellite: span-bearing plans JSON round-trip with hash
    verification for every registered planner; spans survive exactly."""
    from repro.api import get_strategy
    mms = _mm_batch(3)
    strat = get_strategy(name, plan_cache=False).bind(CM, 8, 3000.0)
    plan = strat.plan(mms)
    assert plan.seq_spans and set(plan.seq_spans) == \
        {m.seq_id for m in mms}
    obj = json.loads(json.dumps(plan.to_json()))   # through real JSON
    back = ExecutionPlan.from_json(obj)            # verifies the hash
    assert back.seq_spans == plan.seq_spans
    assert back.structural_hash() == plan.structural_hash()
    # tampering with the span table must break the hash
    bad = plan.to_json()
    key = next(iter(bad["seq_spans"]))
    bad["seq_spans"][key][0][2] += 1
    with pytest.raises(ValueError, match="hash mismatch"):
        ExecutionPlan.from_json(bad)


def test_spanless_plans_hash_like_v2():
    """A plan without spans keeps the exact pre-span hash blob, so
    traces saved by the v2 IR still verify."""
    import hashlib
    from repro.api import get_strategy
    seqs = [SeqInfo(length=n, seq_id=i)
            for i, n in enumerate((128, 700, 1900))]
    plan = get_strategy("dhp", plan_cache=False).bind(
        CM, 8, 3000.0).plan(seqs)
    assert plan.seq_spans is None
    tree = [[[list(g.seq_ids), g.degree] for g in mb.groups]
            for mb in plan.micro_batches]
    want = hashlib.sha256(json.dumps(
        tree, separators=(",", ":")).encode()).hexdigest()[:16]
    assert plan.structural_hash() == want


def test_replay_preserves_recorded_plan_spans_and_hash():
    """A recorded plan's span table (or its absence) is part of the
    hash the trace was saved with — replay must NOT re-derive it from
    the incoming batch."""
    from repro.api import ReplayStrategy, get_strategy
    mms = _mm_batch(9)
    strat = get_strategy("dhp", plan_cache=False).bind(CM, 8, 3000.0)
    recorded = strat.plan(mms)
    want = recorded.structural_hash()
    # span-bearing plan replayed -> identical hash and spans
    rs = ReplayStrategy(plans=[ExecutionPlan.from_json(
        recorded.to_json())]).bind(CM, 8, 3000.0)
    replayed = rs.plan(mms)
    assert replayed.structural_hash() == want
    assert replayed.seq_spans == recorded.seq_spans
    # a v2-style SPAN-FREE recorded plan replayed against a span-bearing
    # stream keeps hashing like v2 (spans are not grafted on)
    bare = ExecutionPlan.from_json(recorded.to_json())
    bare.seq_spans = None
    v2_hash = bare.structural_hash()
    rs2 = ReplayStrategy(plans=[bare]).bind(CM, 8, 3000.0)
    replayed2 = rs2.plan(mms)
    assert replayed2.seq_spans is None
    assert replayed2.structural_hash() == v2_hash


def test_executor_causal_batches_keep_pre_span_executables():
    """Scalar (span-free) batches must compile the exact pre-span
    executable keys and ship no modality table — the span machinery is
    pay-for-what-you-use."""
    from repro.configs import get_config
    from repro.core import DHPScheduler
    from repro.core.executor import DHPExecutor
    from repro.data.pipeline import RaggedBatch
    from repro.models.model import init_params
    cfg = get_config("internvl3-2b").reduced().with_(family="dense",
                                                     vlm=None)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    infos = [SeqInfo(length=n, seq_id=i)
             for i, n in enumerate((90, 60, 40))]
    data = RaggedBatch(infos=infos, tokens=[
        rng.integers(0, cfg.vocab, size=s.length).astype(np.int32)
        for s in infos])
    cm = CostModel(dataclasses.replace(CM.coeffs))
    plan = DHPScheduler(cm, 1, mem_budget=400.0).schedule(infos)
    ex = DHPExecutor(cfg, packed=True)
    ex.run_plan(params, plan, data)
    assert ex.last_exe_keys
    for key in ex.last_exe_keys:
        assert key[0] == "pgrad" and "mm" not in key, key


def test_strategy_plan_accepts_mmsequences_directly():
    from repro.api import get_strategy
    mms = _mm_batch(7)
    infos = [m.seq_info for m in mms]
    s1 = get_strategy("dhp", plan_cache=False).bind(CM, 8, 3000.0)
    s2 = get_strategy("dhp", plan_cache=False).bind(CM, 8, 3000.0)
    p1, p2 = s1.plan(mms), s2.plan(infos)
    assert p1.structural_hash() == p2.structural_hash()


# ------------------------------------------------------------ packing
def test_flatten_group_modality_table():
    seqs = [np.arange(6, dtype=np.int32),
            np.arange(5, dtype=np.int32) + 50]
    spans = [
        (ModalitySpan("text", 0, 2),
         ModalitySpan("vision", 2, 3, "bidirectional"),
         ModalitySpan("text", 5, 1)),
        (ModalitySpan("audio", 0, 4, "bidirectional"),
         ModalitySpan("text", 4, 1)),
    ]
    batch, cu = flatten_group(seqs, bucket=16, spans=spans)
    mod = batch["modality_ids"][0]
    np.testing.assert_array_equal(
        mod[:11], [-1, -1, 0, 0, 0, -1, 1, 1, 1, 1, -1])
    assert (mod[11:] == -1).all()
    # distinct blocks got distinct ids (no cross-block bleed)
    assert mod[2] != mod[6]
    # spans omitted (or all None) -> NO modality table: pure-causal
    # batches keep the exact pre-span batch dict and attention path
    batch2, _ = flatten_group(seqs, bucket=16)
    assert "modality_ids" not in batch2
    batch3, _ = flatten_group(seqs, bucket=16, spans=[None, None])
    assert "modality_ids" not in batch3


def test_executor_modality_tokens_and_mixed_mask_parity(subproc):
    """End to end on 8 devices: a span-bearing loader batch executes
    with the mixed mask on BOTH executor paths (packed and padded) with
    equal loss/grads, and StepMetrics reports per-modality tokens."""
    subproc("""
import dataclasses, jax, numpy as np
from repro.api import ClusterSpec, Engine
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
assert all(s.spans for s in data.infos)
coeffs = dataclasses.replace(
    analytic_coeffs(hidden=cfg.d_model, n_layers=cfg.n_layers,
                    n_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
                    ffn=cfg.d_ff, vocab=cfg.vocab), m_ms=0.0, m_token=1.0)
plan = DHPScheduler(CostModel(coeffs), 8, mem_budget=900.0).schedule(
    data.infos)
ex_p = DHPExecutor(cfg, packed=True)
ex_u = DHPExecutor(cfg, packed=False)
l_p, g_p = ex_p.run_plan(params, plan, data)
l_u, g_u = ex_u.run_plan(params, plan, data)
assert abs(float(l_p) - float(l_u)) < 2e-5, (float(l_p), float(l_u))
err = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
          for a, b in zip(jax.tree.leaves(g_p), jax.tree.leaves(g_u)))
assert err < 1e-4, err

# the mask is REAL: stripping the spans changes the loss
stripped = dataclasses.replace(data, infos=[
    dataclasses.replace(s, spans=None) for s in data.infos])
l_c, _ = ex_p.run_plan(params, plan, stripped)
assert abs(float(l_p) - float(l_c)) > 1e-6, (float(l_p), float(l_c))

# engine-level telemetry
eng = Engine(cfg, ClusterSpec.auto(mem_budget=900.0), strategy="dhp",
             seed=0)
hist = eng.train(steps=1, dataset="openvid", global_batch=6,
                 max_tokens=256, tokens_per_frame=16)
mt = hist[0].modality_tokens
assert mt.get("vision", 0) > 0 and mt.get("text", 0) > 0
assert sum(mt.values()) == hist[0].tokens
print("mixed-mask parity ok", err, mt)
""", n_devices=8)


# ------------------------------------------------------------ serving
def test_serving_scheduler_never_splits_bidirectional_blocks():
    from repro.api import demo_cost_model, get_strategy
    from repro.configs import get_config
    from repro.serving.kv_cache import KVCacheManager
    from repro.serving.scheduler import (ContinuousBatchingScheduler,
                                         ServeRequest)
    cfg = get_config("internvl3-2b").reduced()
    planner = get_strategy("dhp").bind(demo_cost_model(cfg), 1, 4096.0)
    kv = KVCacheManager(2, 64, 16)
    sched = ContinuousBatchingScheduler(kv, planner, prefill_chunk=16)
    spans = (ModalitySpan("text", 0, 10),
             ModalitySpan("vision", 10, 30, "bidirectional"),
             ModalitySpan("text", 40, 25))
    req = ServeRequest(request_id=0,
                       tokens=np.arange(65, dtype=np.int32),
                       max_new_tokens=4, spans=spans)
    sched.submit(req)
    seen = []
    while any(s.status == "prefill" for s in sched.states.values()) \
            or sched.queue:
        it = sched.step()
        for g in it.prefill_groups:
            for c in g.chunks:
                seen.append((c.start, c.length))
                sched.mark_prefilled(c.request_id, c.length)
    # every bidirectional block fully inside one chunk
    for start, length in seen:
        end = start + length
        assert not (10 < end < 40) or end >= 40, seen
    assert sum(ln for _, ln in seen) == req.prompt_len - 1
    # chunk SeqInfos derived their eta from the chunk's own spans: the
    # plan carried span tables
    assert sched.plans_validated >= 1


def test_span_aware_chunked_prefill_invariant_to_chunking():
    """Serving acceptance: span-aware chunked prefill produces the SAME
    KV cache whatever the chunking (chunks snapped to span boundaries),
    and a DIFFERENT cache than causal-only prefill — the vision block
    is really masked."""
    from repro.configs import get_config
    from repro.models.model import init_cache, init_params, prefill_chunk
    cfg = get_config("internvl3-2b").reduced().with_(
        family="dense", vlm=None)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    L, T = 48, 64
    toks = rng.integers(0, cfg.vocab, size=(1, L)).astype(np.int32)
    spans = (ModalitySpan("text", 0, 8),
             ModalitySpan("vision", 8, 24, "bidirectional"),
             ModalitySpan("text", 32, 16))
    row = np.full((1, T), -1, np.int32)
    row[0, 8:32] = 0

    def run(chunking):
        cache = init_cache(cfg, 1, T)
        for s, c in chunking:
            cs = np.full((1, c), -1, np.int32)
            cs[0] = row[0, s:s + c]
            cache = prefill_chunk(
                params, cfg, cache, jnp.asarray(toks[:, s:s + c]), s,
                span_ids=jnp.asarray(cs),
                cache_span_ids=jnp.asarray(row))
        return cache

    one = run([(0, 48)])
    # chunk boundaries at 8 and 32 = span boundaries (scheduler snap)
    many = run([(0, 8), (8, 24), (32, 16)])
    np.testing.assert_allclose(np.asarray(one["k"][:, :, :L]),
                               np.asarray(many["k"][:, :, :L]),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(one["v"][:, :, :L]),
                               np.asarray(many["v"][:, :, :L]),
                               atol=1e-4)
    causal = init_cache(cfg, 1, T)
    causal = prefill_chunk(params, cfg, causal, jnp.asarray(toks), 0)
    # layer 0 K is mask-independent; deeper layers must differ
    assert float(np.abs(np.asarray(one["k"][1:, :, :L])
                        - np.asarray(causal["k"][1:, :, :L])).max()) \
        > 1e-5


def test_sample_trace_carries_spans_and_serving_runs():
    from repro.api import Engine, sample_trace
    rng = np.random.default_rng(5)
    trace = sample_trace("openvid", 3, rng, max_prompt=64,
                         mean_new_tokens=3, max_new_tokens=4)
    for r in trace:
        assert r.spans is not None
        assert sum(sp.length for sp in r.spans) == r.prompt_len
        assert r.eta == pytest.approx(spans_eta(r.spans))
    assert any(any(sp.attn == "bidirectional" for sp in r.spans)
               for r in trace)
    legacy = sample_trace("openvid", 3, np.random.default_rng(5),
                          max_prompt=64, with_spans=False)
    assert all(r.spans is None for r in legacy)
    # span-bearing trace serves to completion through the runtime
    eng = Engine("internvl3-2b", strategy="dhp", reduced=True, seed=0)
    rep = eng.serving(slots=2, prefill_chunk=16).run(trace)
    assert len(rep.requests) == len(trace)
    assert all(m.n_generated > 0 for m in rep.requests)


# ------------------------------------------------------ loss masking (PR 7)
def test_fill_loss_row_semantics():
    from repro.core.packing import (MODALITY_CLASSES, fill_loss_row,
                                    modality_class)
    L = 8
    cls = np.full(L, -1, np.int32)
    lm = np.zeros(L, np.float32)
    lm[:L - 1] = 1.0                       # base next-token mask
    spans = (ModalitySpan("text", 0, 2),
             ModalitySpan("vision", 2, 3, "bidirectional"),
             ModalitySpan("text", 5, 3))
    fill_loss_row(cls, lm, spans, 0, L)
    # position i labels token i+1: the vision span [2, 5) owns label
    # positions [1, 4), which are excluded from the NLL...
    np.testing.assert_array_equal(lm, [1, 0, 0, 0, 1, 1, 1, 0])
    # ...but still classified for telemetry; everything else is text
    v = modality_class("vision")
    np.testing.assert_array_equal(cls, [0, v, v, v, 0, 0, 0, -1])
    assert MODALITY_CLASSES[v] == "vision"
    # unknown modalities fold into "other", never crash
    assert MODALITY_CLASSES[modality_class("thermal")] == "other"


def test_flatten_group_and_padded_batch_loss_mask_agree():
    from repro.data.pipeline import padded_batch
    seqs = [np.arange(6, dtype=np.int32),
            np.arange(5, dtype=np.int32) + 50]
    spans = [
        (ModalitySpan("text", 0, 2),
         ModalitySpan("vision", 2, 3, "bidirectional"),
         ModalitySpan("text", 5, 1)),
        (ModalitySpan("audio", 0, 4, "bidirectional"),
         ModalitySpan("text", 4, 1)),
    ]
    flat, cu = flatten_group(seqs, bucket=16, spans=spans)
    pad = padded_batch(seqs, bucket=8, spans=spans)
    for batch in (flat, pad):
        assert batch["loss_mask"].shape == batch["mask"].shape
        # loss_mask only ever REMOVES label positions
        assert ((batch["mask"] - batch["loss_mask"]) >= 0).all()
        # a class everywhere a label exists, -1 where none
        assert ((batch["modality_classes"] >= 0)
                == (batch["mask"] > 0)).all()
    # same per-sequence semantics on both layouts
    for i in range(len(seqs)):
        a, b = int(cu[i]), int(cu[i + 1])
        L = b - a
        np.testing.assert_array_equal(flat["loss_mask"][0, a:b],
                                      pad["loss_mask"][i, :L])
        np.testing.assert_array_equal(flat["modality_classes"][0, a:b],
                                      pad["modality_classes"][i, :L])
    # bidirectional audio prefix of seq 1: labels [0, 3) masked out
    np.testing.assert_array_equal(pad["loss_mask"][1, :5],
                                  [0, 0, 0, 1, 0])
    # span-less call emits NEITHER table (pre-span dict preserved)
    assert "loss_mask" not in padded_batch(seqs, bucket=8)


def test_engine_reports_modality_loss_and_replan_telemetry(subproc):
    """Engine-level PR-7 telemetry on 8 devices: per-modality NLL from
    the loss-masked executor, Stage-2 allocate_us, replan_mode, and the
    depth-k batched lookahead window."""
    subproc("""
from repro.api import ClusterSpec, Engine, get_strategy
from repro.core.packing import MODALITY_CLASSES
from repro.data.pipeline import HeterogeneousLoader

loader = HeterogeneousLoader("openvid", 6, 512, seed=3, max_tokens=256,
                             tokens_per_frame=16)
data = next(iter(loader))

# plan_cache OFF + a REPEATED batch: step 1 solves cold ("full"),
# steps 2-3 re-solve the identical instance off the warm DP state
eng = Engine("internvl3-2b", ClusterSpec.auto(mem_budget=900.0),
             reduced=True, seed=0,
             strategy=get_strategy("dhp", plan_cache=False))
hist = eng.train(loader=iter([data, data, data]), steps=3, lookahead=2)
m0 = hist[0]
# span-bearing openvid batches report per-modality NLL; bidirectional
# vision labels are excluded from the TRAINING loss but still reported
assert set(m0.modality_loss) <= set(MODALITY_CLASSES)
assert "text" in m0.modality_loss and "vision" in m0.modality_loss
assert all(v > 0 for v in m0.modality_loss.values())
assert m0.allocate_us > 0
assert m0.replan_mode == "full"
assert all(m.replan_mode == "incremental" for m in hist[1:]), \
    [m.replan_mode for m in hist]
eng.close()

# plan_cache ON: the repeated shape is served from the PlanCache
eng2 = Engine("internvl3-2b", ClusterSpec.auto(mem_budget=900.0),
              reduced=True, seed=0,
              strategy=get_strategy("dhp", plan_cache=True))
hist2 = eng2.train(loader=iter([data, data]), steps=2, lookahead=False)
assert hist2[1].plan_cache_hit and hist2[1].replan_mode == "cache"
eng2.close()
print("telemetry ok", m0.modality_loss, [m.replan_mode for m in hist])
""", n_devices=8)


def test_strategy_prepare_many_window_matches_cold_plans():
    from repro.api import get_strategy
    batches = [[m.seq_info for m in _mm_batch(seed, n=8)]
               for seed in (1, 2, 3)]
    strat = get_strategy("dhp", plan_cache=False).bind(CM, 8, 3000.0)
    strat.prepare_many(batches)
    assert strat.n_pending == 3
    window = [strat.collect() for _ in range(3)]
    strat.close()
    for infos, plan in zip(batches, window):
        cold = get_strategy("dhp", plan_cache=False).bind(
            CM, 8, 3000.0).plan(infos)
        assert plan.structural_hash() == cold.structural_hash()


def test_new_dataset_profiles_span_layouts():
    """PR-7 profiles: image-QA is a single bidirectional vision prefix
    (n_images x 576 patch tokens) + causal QA text; long-form audio is
    one bidirectional audio window + causal transcript — and both feed
    the planner the derived (not hand-set) eta."""
    rng = np.random.default_rng(0)
    qa = sample_mm_batch("imageqa", 32, rng)
    for m in qa:
        bidi = [sp for sp in m.spans if sp.attn == "bidirectional"]
        assert len(bidi) == 1 and bidi[0].modality == "vision"
        assert bidi[0].start == 0 and bidi[0].length % 576 == 0
        assert 1 <= bidi[0].length // 576 <= 4
        assert m.spans[-1].attn == "causal"          # QA text tail
        assert m.eta == pytest.approx(spans_eta(m.spans))
    au = sample_mm_batch("longaudio", 32, rng)
    lens = sorted(m.length for m in au)
    for m in au:
        bidi = [sp for sp in m.spans if sp.attn == "bidirectional"]
        assert len(bidi) == 1 and bidi[0].modality == "audio"
        assert bidi[0].start == 0
    # 30 s .. 15 min at 25 tok/s + 400 transcript tokens
    assert lens[0] >= 30 * 25 + 400
    assert lens[-1] <= 900 * 25 + 400
    # the long tail the profile exists for: >4x spread in one batch
    assert lens[-1] / lens[0] > 4
