"""The executor's host-side spans: inside every `collect` span one
`collect.sum` per group (the dispatch of its on-device accumulate) and
nothing else, no gradient transfer spans, the bytes that cross to the
accumulator's devices, float32 results bit-identical with tracing on
and off, tracing without measuring mode, and the jit names the chip
benchmark's device-trace readers match."""
import json

import pytest

from repro.api.engine import UPDATE_MODULE
from repro.core.executor import GRAD_ACCUM_MODULE, GRAD_STEP_MODULE

SCRIPT = r"""
import json, re
import jax
import numpy as np
from repro.api import ClusterSpec, Engine, get_strategy
from repro.configs import get_config
from repro.core.executor import DHPExecutor, accumulate_grads
from repro.core.scheduler import ExecutionPlan, GroupPlan, MicroBatchPlan
from repro.data.pipeline import HeterogeneousLoader
from repro.obs import Tracer, tracing
from repro.obs.trace import PID_RANKS

cfg = get_config("internvl3-2b").reduced().with_(
    family="dense", vlm=None, d_model=64, n_heads=4, kv_heads=2,
    d_ff=256, vocab=512, n_layers=2, param_dtype="bfloat16")
eng = Engine(cfg, ClusterSpec.auto(mem_budget=500.0), seed=0,
             strategy=get_strategy("dhp"))
loader = HeterogeneousLoader("openvid", 16, cfg.vocab, seed=3,
                             max_tokens=450, tokens_per_frame=16)
data = next(iter(loader))
plan = eng.plan(data)
ex = eng.executor
params = eng.state.params

loss_off, g_off = ex.run_plan(params, plan, data)     # NullTracer
tracer = Tracer()
with tracing(tracer):
    loss_on, g_on = ex.run_plan(params, plan, data)
spans = [e for e in tracer.to_json()["traceEvents"] if e["ph"] == "X"]

def inside(c, e):
    return c["ts"] <= e["ts"] and e["ts"] + e["dur"] <= c["ts"] + c["dur"]

children = ("collect.wait", "collect.d2h", "collect.sum")
off_home = sum((start, degree) != (0, 1)
               for *_, start, degree in plan.group_slots(4))
collects = []
for c in (e for e in spans if e["name"] == "collect"):
    kids = [e for e in spans if e["name"] in children and inside(c, e)]
    collects.append({
        "groups": c["args"]["groups"], "dur": c["dur"],
        "kids_dur": sum(e["dur"] for e in kids),
        "counts": {n: sum(e["name"] == n for e in kids) for n in children}})

leaves_on = jax.tree.leaves(g_on)
leaves_off = jax.tree.leaves(g_off)

# the module names of the grad step, the update and the accumulate,
# lowered on the CPU
g = plan.micro_batches[0].groups[0]
np_batch, _, _, bucket = ex._group_batch(
    [data.by_id(i) for i in g.seq_ids], 1,
    spans=[data.spans_by_id().get(i) for i in g.seq_ids])
step, _, _ = ex._packed_grad_fn(0, 1, bucket, "modality_ids" in np_batch)
batch = {k: jax.numpy.asarray(v) for k, v in np_batch.items()}

def module(lowered):
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)

out = step(params, batch)
modules = {"grad": module(step.lower(params, batch)),
           "update": module(eng.update_fn().lower(eng.state, g_off)),
           "accumulate": module(accumulate_grads.lower(None, out, 1.0))}

# one device: every group runs where the accumulator lives
ex1 = DHPExecutor(cfg, devices=jax.devices()[:1])
ids = [s.seq_id for s in data.infos]
ex1.run_plan(params, ExecutionPlan(
    [MicroBatchPlan([GroupPlan(ids[i:i + 4], 1, 0.0, 0)], 0.0, 1)
     for i in range(0, len(ids), 4)], 0.0, 0.0, 0.0), data)

res = {
    "groups": sum(len(mb.groups) for mb in plan.micro_batches),
    "collects": collects,
    "child_total": sum(e["name"] in children for e in spans),
    "n_run_plan": sum(e["name"] == "run_plan" for e in spans),
    "names": sorted({e["name"] for e in spans}),
    "off_home": off_home,
    "d2d_bytes": ex.last_run_stats["grad_d2d_bytes"],
    "d2d_bytes_one_device": ex1.last_run_stats["grad_d2d_bytes"],
    "out_bytes": sum(a.nbytes for a in jax.tree.leaves(out)),
    "grad_dtypes": sorted({str(a.dtype) for a in leaves_on}),
    "bf16_bytes": sum(np.asarray(a).nbytes
                      for a in jax.tree.leaves(params)),
    "param_dtypes": sorted({str(a.dtype)
                            for a in jax.tree.leaves(params)}),
    "loss_same": np.asarray(loss_on).tobytes()
                 == np.asarray(loss_off).tobytes(),
    "grads_same": jax.tree.structure(g_on) == jax.tree.structure(g_off)
                  and all(a.dtype == b.dtype and
                          np.asarray(a).tobytes() == np.asarray(b).tobytes()
                          for a, b in zip(leaves_on, leaves_off)),
    "modules": modules,
}

# tracing alone keeps the concurrent schedule: no measuring mode (the
# update donates, and so deletes, `params`)
tr_train = Tracer()
loader = HeterogeneousLoader("openvid", 16, cfg.vocab, seed=4,
                             max_tokens=450, tokens_per_frame=16)
eng.train(loader=iter(loader), steps=2, lookahead=True, trace=tr_train)
train_events = [e for e in tr_train.to_json()["traceEvents"]
                if e["ph"] == "X"]
res.update(
    train_names=sorted({e["name"] for e in train_events}),
    train_rank_spans=sum(e["pid"] == PID_RANKS for e in train_events),
    last_report_none=eng.last_report is None)
print(json.dumps(res))
eng.close()
"""


@pytest.fixture(scope="module")
def traced(subproc):
    out = subproc(SCRIPT, n_devices=4)
    info = json.loads(out.strip().splitlines()[-1])
    assert info["groups"] >= 2, info        # several groups to drain
    return info


def test_collect_holds_one_sum_per_group(traced):
    assert traced["collects"]
    for c in traced["collects"]:
        assert c["counts"] == {"collect.wait": 0, "collect.d2h": 0,
                               "collect.sum": c["groups"]}, c
        assert c["kids_dur"] <= c["dur"]
    # every child span lies inside a collect span
    assert traced["child_total"] == traced["groups"]


def test_no_gradient_transfer_spans(traced):
    assert traced["n_run_plan"] == 1
    for name in ("grad_upload", "collect.wait", "collect.d2h"):
        assert name not in traced["names"]


def test_d2d_bytes_are_the_moved_outputs(traced):
    # a group off device 0, where the parameters live, sends its whole
    # output over: loss, bf16 gradients and, as every group of this
    # batch carries video spans, the modality table
    assert traced["off_home"] > 0
    assert traced["out_bytes"] > traced["bf16_bytes"]
    assert traced["d2d_bytes"] == traced["off_home"] * traced["out_bytes"]


def test_d2d_bytes_zero_on_one_device(traced):
    assert traced["d2d_bytes_one_device"] == 0


def test_gradients_stay_float32(traced):
    assert traced["param_dtypes"] == ["bfloat16"]
    assert traced["grad_dtypes"] == ["float32"]


def test_results_bit_identical_with_and_without_tracer(traced):
    assert traced["loss_same"]
    assert traced["grads_same"]


def test_tracing_alone_does_not_switch_measuring_mode(traced):
    assert "dispatch" in traced["train_names"]
    assert "collect.sum" in traced["train_names"]
    assert "execute" not in traced["train_names"]
    assert traced["train_rank_spans"] == 0
    assert traced["last_report_none"]


@pytest.mark.parametrize("program,name,constant", [
    ("grad", "jit_fwd_bwd", GRAD_STEP_MODULE),
    ("update", "jit_apply_update", UPDATE_MODULE),
    ("accumulate", "jit_accumulate_grads", GRAD_ACCUM_MODULE)])
def test_jit_names_the_benchmark_reads(traced, program, name, constant):
    assert constant == name
    assert traced["modules"][program] == name
