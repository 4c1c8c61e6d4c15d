"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode (per-kernel allclose against ref.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention import flash_attention_flat
from repro.kernels.ops import flash_attention
from repro.kernels.ref import flash_attention_ref, rglru_scan_ref
from repro.kernels.rglru_scan import rglru_scan_pallas

KEY = jax.random.PRNGKey(0)


def qkv(B, S, H, Hkv, D, dtype=jnp.float32, Skv=None):
    Skv = Skv or S
    q = jax.random.normal(KEY, (B, S, H, D), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, Skv, Hkv, D),
                          dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Skv, Hkv, D),
                          dtype)
    return q, k, v


def test_interpret_mode_follows_backend():
    """Interpret only on the CPU backend; an explicit choice wins."""
    from repro.kernels import interpret_mode
    assert interpret_mode() == (jax.default_backend() == "cpu")
    assert interpret_mode(False) is False
    assert interpret_mode(True) is True


# ------------------------------------------------------------- flash attn
@pytest.mark.parametrize("mode,window", [("causal", None), ("full", None),
                                         ("sliding", 96)])
@pytest.mark.parametrize("S,D,bq,bk", [(128, 64, 64, 64),
                                       (256, 64, 128, 64),
                                       (192, 32, 64, 128)])
def test_flash_shape_sweep(mode, window, S, D, bq, bk):
    q, k, v = qkv(1, S, 2, 1, D)
    out = flash_attention(q, k, v, mode=mode, window=window,
                          block_q=bq, block_k=bk)
    ref = flash_attention(q, k, v, mode=mode, window=window, ref=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_flash_dtype_sweep(dtype, tol):
    q, k, v = qkv(2, 128, 4, 2, 64, dtype)
    out = flash_attention(q, k, v, mode="causal")
    ref = flash_attention(q, k, v, mode="causal", ref=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_unaligned_lengths_padded():
    """Sq/Sk not multiples of the block — the wrapper pads + masks."""
    q, k, v = qkv(1, 100, 2, 2, 32, Skv=100)
    out = flash_attention(q, k, v, mode="causal", block_q=64, block_k=64)
    ref = flash_attention(q, k, v, mode="causal", ref=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_kv_offset_ring_hop():
    """kv_offset makes the kernel compute one ring-attention hop: local
    queries vs a KV block owned by another rank."""
    B, S, D = 1, 128, 32
    q = jax.random.normal(KEY, (B, S, D))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, D))
    # Hop where the incoming KV block is entirely in the PAST: queries at
    # global [128, 256), kv at [0, 128) -> kv_offset = 0 - 128 = -128.
    # Every kv position is attendable, so one hop == full softmax over
    # this block.
    out = flash_attention_flat(q, k, v, mode="causal",
                               block_q=64, block_k=64, kv_offset=-128)
    s = (np.asarray(q[0], np.float64) @ np.asarray(k[0], np.float64).T
         / np.sqrt(D))
    p = np.exp(s - s.max(-1, keepdims=True))
    ref = (p / p.sum(-1, keepdims=True)) @ np.asarray(v[0], np.float64)
    np.testing.assert_allclose(np.asarray(out[0], np.float64), ref,
                               atol=1e-4, rtol=1e-4)

    # Hop where the incoming KV block is entirely in the FUTURE: queries
    # at [0, 128), kv at [128, 256) -> kv_offset = +128. Nothing is
    # attendable under the causal mask; the l=0 guard emits zeros.
    out_f = flash_attention_flat(q, k, v, mode="causal",
                                 block_q=64, block_k=64, kv_offset=128)
    np.testing.assert_allclose(np.asarray(out_f), 0.0, atol=0.0)


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 3), st.sampled_from([64, 96, 160]),
       st.sampled_from([32, 64]))
def test_flash_property_random_shapes(B, S, D):
    q, k, v = qkv(B, S, 2, 2, D)
    out = flash_attention(q, k, v, mode="causal", block_q=64, block_k=64)
    ref = flash_attention(q, k, v, mode="causal", ref=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


# ------------------------------------------------------------- rglru scan
@pytest.mark.parametrize("S,W,chunk", [(64, 32, 16), (100, 16, 32),
                                       (128, 128, 64)])
def test_rglru_scan_sweep(S, W, chunk):
    a = jax.random.uniform(KEY, (2, S, W), minval=0.3, maxval=0.99)
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (2, S, W)) * 0.1
    out = rglru_scan_pallas(a, b, chunk=chunk)
    ref = rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_rglru_scan_dtype_bf16():
    a = jax.random.uniform(KEY, (1, 64, 32), minval=0.5,
                           maxval=0.95).astype(jnp.bfloat16)
    b = (jax.random.normal(jax.random.fold_in(KEY, 1), (1, 64, 32))
         * 0.1).astype(jnp.bfloat16)
    out = rglru_scan_pallas(a, b, chunk=32)
    ref = rglru_scan_ref(a.astype(jnp.float32), b.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=5e-2, rtol=5e-2)


# ------------------------------------------------------------- ssd chunk
def _ssd_inputs(G, c, N, P, dtype=jnp.float32, key=7):
    k = jax.random.fold_in(KEY, key)
    ks = jax.random.split(k, 5)
    C = jax.random.normal(ks[0], (G, c, N), dtype) * 0.3
    B = jax.random.normal(ks[1], (G, c, N), dtype) * 0.3
    x = jax.random.normal(ks[2], (G, c, P), dtype)
    # da = dt*A with A<0: keep decays in a numerically sane range
    dt = jax.nn.softplus(jax.random.normal(ks[3], (G, c))) + 1e-3
    da = -dt * jax.random.uniform(ks[4], (G, c), minval=0.05, maxval=1.0)
    return C, B, x, da.astype(dtype), dt.astype(dtype)


@pytest.mark.parametrize("G,c,N,P", [(3, 64, 32, 16), (2, 128, 128, 64),
                                     (1, 128, 64, 128), (4, 32, 16, 8)])
def test_ssd_chunk_shape_sweep(G, c, N, P):
    from repro.kernels.ref import ssd_chunk_ref
    from repro.kernels.ssd_chunk import ssd_chunk_pallas
    C, B, x, da, dt = _ssd_inputs(G, c, N, P)
    y, st, cum = ssd_chunk_pallas(C, B, x, da, dt)
    yr, str_, cumr = ssd_chunk_ref(C, B, x, da, dt)
    np.testing.assert_allclose(np.asarray(cum), np.asarray(cumr),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(str_),
                               atol=1e-4, rtol=1e-4)


def test_ssd_chunk_dtype_bf16():
    from repro.kernels.ref import ssd_chunk_ref
    from repro.kernels.ssd_chunk import ssd_chunk_pallas
    C, B, x, da, dt = _ssd_inputs(2, 64, 32, 16, dtype=jnp.bfloat16)
    y, st, _ = ssd_chunk_pallas(C, B, x, da, dt)
    yr, str_, _ = ssd_chunk_ref(C, B, x, da, dt)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=5e-2, rtol=5e-2)
    np.testing.assert_allclose(np.asarray(st, np.float32),
                               np.asarray(str_, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_ssd_chunk_scan_matches_model_ssd():
    """The composed kernel op (intra Pallas + inter scan) must equal the
    models/ssm.py chunked-SSD core on a full multi-chunk sequence."""
    from repro.kernels.ops import ssd_chunk_scan
    Bsz, S, H, P, N, c = 2, 96, 2, 8, 16, 32
    nc, G = S // c, Bsz * H
    k = jax.random.fold_in(KEY, 11)
    ks = jax.random.split(k, 5)
    Cm = jax.random.normal(ks[0], (Bsz, S, N)) * 0.3
    Bm = jax.random.normal(ks[1], (Bsz, S, N)) * 0.3
    xh = jax.random.normal(ks[2], (Bsz, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (Bsz, S, H))) + 1e-3
    A = -jax.random.uniform(ks[4], (H,), minval=0.1, maxval=1.0)

    # oracle: the per-head path from models/ssm.py (sequential scan)
    def seq_ref(b, h):
        hstate = jnp.zeros((N, P))
        ys = []
        for t in range(S):
            a_t = jnp.exp(dt[b, t, h] * A[h])
            hstate = a_t * hstate + dt[b, t, h] * jnp.outer(
                Bm[b, t], xh[b, t, h])
            ys.append(Cm[b, t] @ hstate)
        return jnp.stack(ys)

    # kernel path: [G, nc, c, ...] layout, da = dt*A per head
    def to_g(t):           # [B,S,...] with head -> [G,nc,c,...]
        return t.reshape(Bsz, nc, c, *t.shape[2:])
    Cg = jnp.broadcast_to(to_g(Cm)[:, None], (Bsz, H, nc, c, N)).reshape(
        G, nc, c, N)
    Bg = jnp.broadcast_to(to_g(Bm)[:, None], (Bsz, H, nc, c, N)).reshape(
        G, nc, c, N)
    xg = xh.transpose(0, 2, 1, 3).reshape(Bsz, H, nc, c, P).reshape(
        G, nc, c, P)
    dtg = dt.transpose(0, 2, 1).reshape(Bsz, H, nc, c).reshape(G, nc, c)
    dag = dtg * jnp.repeat(A, Bsz * nc * c).reshape(
        H, Bsz, nc, c).transpose(1, 0, 2, 3).reshape(G, nc, c)
    y = ssd_chunk_scan(Cg, Bg, xg, dag, dtg)
    y = y.reshape(Bsz, H, S, P)
    for b in range(Bsz):
        for h in range(H):
            np.testing.assert_allclose(np.asarray(y[b, h]),
                                       np.asarray(seq_ref(b, h)),
                                       atol=1e-4, rtol=1e-4)


def test_ssm_forward_pallas_impl_matches_jnp():
    """models/ssm.py with impl='pallas' (ssd_chunk kernel) must equal the
    portable jnp path end-to-end through the full Mamba-2 block."""
    from repro.models.ssm import init_ssm, ssm_forward
    D, dS, hd, ex, chunk = 32, 16, 8, 2, 16
    params = init_ssm(jax.random.fold_in(KEY, 21), D, d_state=dS,
                      head_dim=hd, expand=ex, conv_width=4,
                      dtype=jnp.float32)
    x = jax.random.normal(jax.random.fold_in(KEY, 22), (2, 40, D)) * 0.5
    y_jnp = ssm_forward(params, x, d_state=dS, head_dim=hd, expand=ex,
                        chunk=chunk, impl="jnp")
    y_pl = ssm_forward(params, x, d_state=dS, head_dim=hd, expand=ex,
                       chunk=chunk, impl="pallas")
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_jnp),
                               atol=2e-4, rtol=2e-4)
