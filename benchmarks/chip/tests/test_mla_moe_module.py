"""The `mla_moe` model module (Kimi-VL-A3B's decoder as one chip of an
EP-8 deployment) and the expert layer's readers.

* `shapes` is the program's parameter layout, leaf for leaf;
* the model FLOPs and the grouped matmuls' work, worked by hand;
* the correction bias's init rule is seeded and not zero;
* `gmm_ms`, `gmm_roofline` and `expert_pairs_per_token` on a synthetic
  run, and silent on a run of a program that lacks what they read;
* a test-sized configuration of the module trains on the CPU through the
  harness and is `correct` against the reference.
"""
import json
import time

import jax
import numpy as np
import pytest

import harness
import model_init
import tiny_cell
import traffic

CELL = "kimivl-a3b-openvid-1c"
TINY = tiny_cell.DATA.parent / "tiny_moe"


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


def test_shapes_are_the_programs_layout(cell):
    from repro.models.model import init_params

    cfg, model = cell["cfg"], cell["model"]
    ours = jax.eval_shape(lambda: model_init.make_params(
        model, cfg, np.zeros(2, np.uint32)))
    theirs = jax.eval_shape(lambda: init_params(
        jax.random.PRNGKey(0), model.program_config(cfg)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), ours) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), theirs)
    assert sum(a.size for a in jax.tree.leaves(ours)) == 668_890_432


def test_model_flops_by_hand(cell):
    cfg, model = cell["cfg"], cell["model"]
    attn = (2048 * 16 * 192          # wq
            + 2048 * (512 + 64)      # wkv_a
            + 512 * 16 * 256         # wkv_b
            + 16 * 128 * 2048)       # wo
    assert attn == 13_762_560
    dense_ffn = 3 * 2048 * 11264
    expert_layer = (3 * 2048 * 2816   # 2 shared experts
                    + 2048 * 64       # router
                    + 6 * 8 / 64 * 3 * 2048 * 1408)   # held share of 6
    head = 2048 * 20480
    params = 6 * attn + dense_ffn + 5 * expert_layer + head
    assert params == pytest.approx(313.3e6, rel=1e-3)
    assert model.token_flops(cfg) == pytest.approx(6 * params)
    assert model.pair_flops(cfg) == 6 * 16 * (128 + 64 + 128) * 6 == 184_320
    assert model.expert_layers(cfg) == 5


def test_grouped_matmul_work_by_hand(cell):
    cfg, model = cell["cfg"], cell["model"]
    pairs, calls = 3000, 5 * 4          # 5 expert layers, 4 groups
    proj = 2 * 2048 * 1408               # one projection, one pair
    # 3 projections x (forward, remat, gmm for dx, tgmm for dW)
    assert model.gmm_flops(cfg, pairs) == 12 * proj * pairs
    rows = 3 * 2 * (2048 + 1408)         # bf16 rows in and out, 3 projections
    weights = 3 * 2 * 8 * 2048 * 1408    # the held experts' 3 matrices
    assert model.gmm_bytes(cfg, pairs, calls) == 4 * (
        rows * pairs + weights * calls)


def test_router_bias_rule_is_seeded_and_not_zero(cell):
    model = cell["model"]
    rule = model.INIT["layers/moe/router_bias"]
    a = np.asarray(rule(jax.random.PRNGKey(1), (5, 64), np.float32))
    b = np.asarray(rule(jax.random.PRNGKey(1), (5, 64), np.float32))
    c = np.asarray(rule(jax.random.PRNGKey(2), (5, 64), np.float32))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.all(a != 0) and 0.01 < a.std() < 0.04
    cfg = json.loads((TINY / "tiny_moe.json").read_text())
    p = model_init.make_params(model, cfg, traffic.weight_key(7))
    bias = np.asarray(p["layers"]["moe"]["router_bias"], np.float32)
    assert bias.shape == (2, 8) and np.all(bias != 0)
    assert np.all(np.asarray(p["layers"]["ln1"]["scale"]) == 1)


def _run(cell, pairs, groups, ops):
    return {"cfg": cell["cfg"], "tokens": 2 * 16384,
            "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "steps": [{"moe_routed_pairs": p, "groups": g, "real_tokens": 1}
                      for p, g in zip(pairs, groups)],
            "device": {"ops": ops}}


def test_readers_on_a_synthetic_run(cell):
    readers = {m: harness.reader(harness.HERE, m) for m in
               ("gmm_ms", "gmm_roofline", "expert_pairs_per_token")}
    ops = {"gmm.40": 0.010, "gmm.41": 0.006, "tgmm.10": 0.004,
           "fusion.3": 1.0, "gmm_fusion.1": 5.0}
    run = _run(cell, [60000, 62880], [5, 4], ops)
    assert readers["gmm_ms"](run) == pytest.approx(1e3 * 0.020 / 2)
    assert readers["expert_pairs_per_token"](run) == pytest.approx(
        122880 / (2 * 16384 * 5))
    model = cell["model"]
    want = max(model.gmm_flops(run["cfg"], 122880) / 197e12,
               model.gmm_bytes(run["cfg"], 122880, 5 * 9) / 819e9) / 0.020
    assert readers["gmm_roofline"](run) == pytest.approx(100 * want)
    # a program without the kernels or the counter (the dense model, or
    # the parent of this change): nothing to read
    for strip in ({"device": None}, {"device": {"ops": {"fusion.3": 1.0}}},
                  {"steps": [{"groups": 5, "real_tokens": 1}]}):
        quiet = {**run, **strip}
        assert readers["gmm_roofline"](quiet) is None
    assert readers["gmm_ms"]({**run, "device": None}) is None
    assert readers["expert_pairs_per_token"](
        {**run, "steps": [{"groups": 5}]}) is None


def test_tiny_cell_is_correct_on_the_cpu():
    """A test-sized configuration of the module (1 dense + 2 expert
    layers, 4 of 8 experts held) through the harness on the CPU: the
    program's steps against the reference, by the limits its readings
    set (`data/tiny_moe/limits.json`)."""
    cfg = json.loads((TINY / "tiny_moe.json").read_text())
    spec = {**tiny_cell.spec(
        json.loads((TINY / "limits.json").read_text())["limits"]),
        "cfg": cfg, "model": harness.model_of(cfg)}
    spec["cell"] = {**spec["cell"], "config": cfg["name"]}
    res = harness.run_cell(spec, 10, 0.5, False, jax.devices(),
                           harness.peak_of("TPU v5 lite"),
                           time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
