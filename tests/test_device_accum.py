"""The executor's on-device gradient accumulation against the host
arithmetic it replaced: each group's loss and gradients cast to float32,
times the group's loss tokens, added in plan order and divided by the
total, in NumPy. On one device, and on four with a degree-3 ring beside
a degree-1 group and the parameters on the last device, so that groups
run off the accumulator's devices and their gradients cross over. And
the engine's steps after the first compile nothing new."""
import json

import pytest

SCRIPT = r"""
import json
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.core.executor import DHPExecutor
from repro.core.packing import MODALITY_CLASSES
from repro.core.scheduler import ExecutionPlan, GroupPlan, MicroBatchPlan
from repro.data.pipeline import HeterogeneousLoader
from repro.models.model import init_params

cfg = get_config("internvl3-2b").reduced().with_(
    family="dense", vlm=None, d_model=64, n_heads=4, kv_heads=2,
    d_ff=256, vocab=512, n_layers=2, param_dtype="bfloat16")
devices = jax.devices()
params = init_params(jax.random.PRNGKey(0), cfg)
if len(devices) > 1:
    # the accumulator's home: where the update left the parameters
    params = jax.device_put(params, devices[-1])
loader = HeterogeneousLoader("openvid", 12, cfg.vocab, seed=5,
                             max_tokens=300, tokens_per_frame=16)
data = next(iter(loader))
ids = [s.seq_id for s in data.infos]

def group(seq_ids, degree):
    return GroupPlan(seq_ids, degree, 0.0,
                     sum(len(data.by_id(i)) for i in seq_ids))

if len(devices) > 1:
    # a degree-3 ring on devices 0-2 beside a degree-1 group on device 3,
    # then a degree-1 group on device 0
    mbs = [[group(ids[:4], 3), group(ids[4:7], 1)], [group(ids[7:], 1)]]
else:
    mbs = [[group(ids[:5], 1)], [group(ids[5:], 1)]]
plan = ExecutionPlan([MicroBatchPlan(gs, 0.0, sum(g.degree for g in gs))
                      for gs in mbs], 0.0, 0.0, 0.0)
ex = DHPExecutor(cfg)
loss, grads = ex.run_plan(params, plan, data)
stats = ex.last_run_stats

# the reference: the same grad steps, their outputs summed on the host
total, loss_ref, g_ref, aux_ref = 0.0, 0.0, None, None
for mi, gi, start, degree in plan.group_slots(len(devices)):
    g = plan.micro_batches[mi].groups[gi]
    seqs = [data.by_id(i) for i in g.seq_ids]
    spans = [data.spans_by_id().get(i) for i in g.seq_ids]
    np_batch, _, _, bucket = ex._group_batch(seqs, degree, spans=spans)
    step, _, _ = ex._packed_grad_fn(start, degree, bucket,
                                    "modality_ids" in np_batch)
    mesh_params = jax.device_put(
        params, NamedSharding(ex.pool.mesh_for(start, degree), P()))
    out = jax.device_get(step(mesh_params,
                              {k: jax.numpy.asarray(v)
                               for k, v in np_batch.items()}))
    w = float(np_batch.get("loss_mask", np_batch["mask"]).sum())
    total += w
    loss_ref += float(out[0]) * w
    a = np.asarray(out[2], np.float64)
    aux_ref = a if aux_ref is None else aux_ref + a
    g_np = jax.tree.map(lambda a: a.astype(np.float32) * w, out[1])
    g_ref = g_np if g_ref is None else jax.tree.map(np.add, g_ref, g_np)
g_ref = jax.tree.map(lambda a: a / total, g_ref)

leaves, ref_leaves = jax.tree.leaves(grads), jax.tree.leaves(g_ref)
print(json.dumps({
    "n_devices": len(devices),
    "degrees": sorted({d for *_, d in plan.group_slots(len(devices))}),
    "loss": float(loss), "loss_ref": loss_ref / total,
    "same_tree": jax.tree.structure(grads) == jax.tree.structure(g_ref),
    "leaf_gaps": [float(np.abs(np.asarray(a) - b).max()
                        / max(np.abs(b).max(), 1e-30))
                  for a, b in zip(leaves, ref_leaves)],
    "dtypes": sorted({str(a.dtype) for a in leaves}),
    "arrays": all(isinstance(a, jax.Array) for a in leaves),
    "on_param_devices": all(
        a.devices() == p.devices()
        for a, p in zip(leaves, jax.tree.leaves(params))),
    "d2d_bytes": stats["grad_d2d_bytes"],
    "modality_loss": stats["modality_loss"],
    "modality_ref": {name: float(aux_ref[k, 0] / aux_ref[k, 1])
                     for k, name in enumerate(MODALITY_CLASSES)
                     if aux_ref[k, 1] > 0},
}))
"""


@pytest.fixture(scope="module", params=[1, 4], ids=["1dev", "4dev"])
def accum(request, subproc):
    out = subproc(SCRIPT, n_devices=request.param)
    info = json.loads(out.strip().splitlines()[-1])
    assert info["n_devices"] == request.param
    if request.param > 1:
        assert info["degrees"] == [1, 3], info
    return info


def test_matches_the_host_arithmetic(accum):
    assert accum["same_tree"]
    assert accum["leaf_gaps"] and max(accum["leaf_gaps"]) <= 1e-6, accum
    assert accum["loss"] == pytest.approx(accum["loss_ref"], rel=1e-6)


def test_gradients_are_float32_arrays_on_the_parameters_devices(accum):
    assert accum["dtypes"] == ["float32"]
    assert accum["arrays"]
    assert accum["on_param_devices"]


def test_d2d_bytes_count_groups_off_the_accumulator(accum):
    if accum["n_devices"] == 1:
        assert accum["d2d_bytes"] == 0
    else:
        assert accum["d2d_bytes"] > 0


def test_modality_loss_matches_the_reference(accum):
    ref = accum["modality_ref"]
    assert ref and set(accum["modality_loss"]) == set(ref)
    for name, value in ref.items():
        assert accum["modality_loss"][name] == pytest.approx(value,
                                                             rel=1e-6)


def test_steps_after_the_first_compile_nothing():
    """The engine commits its state where the executor sums the
    gradients, so from the first step on the grad step, the accumulate
    and the update see one placement and are not traced again, though
    the weights came in uncommitted."""
    import jax

    from repro.api import ClusterSpec, Engine
    from repro.configs import get_config
    from repro.core.executor import accumulate_grads
    from repro.data.pipeline import HeterogeneousLoader
    from repro.models.model import init_params
    from repro.training.train_step import TrainState

    cfg = get_config("internvl3-2b").reduced().with_(
        family="dense", vlm=None, d_model=64, n_heads=4, kv_heads=2,
        d_ff=256, vocab=512, n_layers=2, param_dtype="bfloat16")
    eng = Engine(cfg, ClusterSpec(devices=jax.devices()[:1],
                                  mem_budget=500.0), strategy="dhp")
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(0))
    assert not jax.tree.leaves(params)[0].committed
    eng.state = TrainState(params=params, opt=eng.optimizer.init(params))
    data = next(iter(HeterogeneousLoader("openvid", 8, cfg.vocab, seed=5,
                                         max_tokens=300,
                                         tokens_per_frame=16)))
    plan = eng.plan(data)
    eng.execute(plan, data)
    fns = [accumulate_grads, eng.update_fn()] + [
        eng.executor.pool._exes[k] for k in eng.executor.last_exe_keys]
    sizes = [f._cache_size() for f in fns]
    for _ in range(2):
        eng.execute(plan, data)
    assert [f._cache_size() for f in fns] == sizes
    eng.close()
