"""Ring attention + CP executor: multi-device tests (subprocess with a
forced host-device count so the main pytest process keeps 1 device)."""
import pytest


def test_ring_attention_non_power_of_two(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.parallel.ring_attention import ring_attention
from repro.models.attention import attn_reference

devs = jax.devices()
for d_cp in (3, 5, 6):
    mesh = Mesh(np.array(devs[:d_cp]), ("cp",))
    B,S,H,Hkv,Dh = 2, 30*d_cp, 4, 2, 16
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key,(B,S,H,Dh))
    k = jax.random.normal(jax.random.fold_in(key,1),(B,S,Hkv,Dh))
    v = jax.random.normal(jax.random.fold_in(key,2),(B,S,Hkv,Dh))
    pos = jnp.tile(jnp.arange(S)[None],(B,1))
    fm = jax.shard_map(
        lambda q,k,v,p: ring_attention(q,k,v,p,axis_name="cp"),
        mesh=mesh,
        in_specs=(P(None,"cp"),)*4, out_specs=P(None,"cp"))
    out = fm(q,k,v,pos)
    ref = attn_reference(q,k,v,mode="causal")
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)
    print("cp", d_cp, "ok")
""", n_devices=6)
    assert "cp 5 ok" in out


def test_ring_attention_gradients(subproc):
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.parallel.ring_attention import ring_attention
from repro.models.attention import attn_reference

devs = jax.devices()
mesh = Mesh(np.array(devs[:3]), ("cp",))
B,S,H,Hkv,Dh = 1, 48, 2, 1, 8
key = jax.random.PRNGKey(0)
q = jax.random.normal(key,(B,S,H,Dh))
k = jax.random.normal(jax.random.fold_in(key,1),(B,S,Hkv,Dh))
v = jax.random.normal(jax.random.fold_in(key,2),(B,S,Hkv,Dh))
pos = jnp.tile(jnp.arange(S)[None],(B,1))
fm = jax.shard_map(
    lambda q,k,v,p: ring_attention(q,k,v,p,axis_name="cp"),
    mesh=mesh, in_specs=(P(None,"cp"),)*4, out_specs=P(None,"cp"))
g1 = jax.grad(lambda q,k,v: (fm(q,k,v,pos)**2).sum(), argnums=(0,1,2))(q,k,v)
g2 = jax.grad(lambda q,k,v: (attn_reference(q,k,v,mode="causal")**2).sum(),
              argnums=(0,1,2))(q,k,v)
for a,b in zip(g1,g2):
    np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)
print("grads ok")
""", n_devices=3)


def test_ring_decode_distributed_softmax(subproc):
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.parallel.ring_attention import ring_decode_attention
from repro.models.attention import attn_decode
devs = jax.devices()
mesh = Mesh(np.array(devs[:4]), ("cp",))
B,T,H,Hkv,Dh = 2, 64, 4, 2, 16
key = jax.random.PRNGKey(1)
q1 = jax.random.normal(key,(B,1,H,Dh))
kc = jax.random.normal(jax.random.fold_in(key,1),(B,T,Hkv,Dh))
vc = jax.random.normal(jax.random.fold_in(key,2),(B,T,Hkv,Dh))
gm = jax.shard_map(
    lambda q1,kc,vc: ring_decode_attention(
        q1,kc,vc,jnp.full((q1.shape[0],), kc.shape[1]),axis_name="cp"),
    mesh=mesh, in_specs=(P(),P(None,"cp"),P(None,"cp")), out_specs=P())
out = gm(q1,kc,vc)
ref = attn_decode(q1,kc,vc,jnp.full((B,),T))
np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)
print("ok")
""", n_devices=4)


def test_executor_dynamic_equals_static(subproc):
    """The paper's correctness invariant: dynamic regrouping changes
    WHERE sequences run, not the gradient."""
    subproc("""
import jax, numpy as np, dataclasses
from repro.configs import get_config
from repro.core import CostModel, analytic_coeffs, DHPScheduler
from repro.core.executor import DHPExecutor
from repro.core.scheduler import static_plan
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
                    ffn=cfg.d_ff, vocab=cfg.vocab),
    m_ms=0.0, m_token=1.0)
cm = CostModel(coeffs)
ex = DHPExecutor(cfg)
plan = DHPScheduler(cm, 8, mem_budget=900.0).schedule(data.infos)
assert any(g.degree > 1 for mb in plan.micro_batches for g in mb.groups)
l_d, g_d = ex.run_plan(params, plan, data)
l_s, g_s = ex.run_plan(params,
                       static_plan(data.infos, cm, 8, 900.0), data)
assert abs(float(l_d) - float(l_s)) < 2e-5
err = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
          for a, b in zip(jax.tree.leaves(g_d), jax.tree.leaves(g_s)))
assert err < 1e-4, err
assert ex.pool.stats.mesh_misses > 0
print("equivalence ok", err)
""", n_devices=8)


def test_group_pool_caches_meshes_and_executables():
    import numpy as np
    import jax
    from repro.core.group_pool import GroupPool, pow2_bucket
    pool = GroupPool(jax.devices() * 8, model_axis=1)  # fake 8 replicas
    m1 = pool.mesh_for(0, 2)
    m2 = pool.mesh_for(0, 2)
    assert m1 is m2
    assert pool.stats.mesh_hits == 1
    calls = []
    e1, miss1 = pool.executable_for(("k", 1),
                                    lambda: calls.append(1) or "exe")
    e2, miss2 = pool.executable_for(("k", 1),
                                    lambda: calls.append(1) or "exe")
    assert e1 == e2 and len(calls) == 1
    assert miss1 and not miss2
    assert pow2_bucket(100) == 128
    assert pow2_bucket(128) == 128
    assert pow2_bucket(129) == 256
