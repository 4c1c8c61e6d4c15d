"""The harness: cells found by name, no result without a chip, the
check on the tiny cell, and `correct` false for each fault a one-chip
training cell can have."""
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import harness
import model_init
import tiny_cell

ROOT = harness.ROOT


def _run(spec, seed=3):
    import time
    peak = harness.peak_of("TPU v5 lite")
    return harness.run_cell(spec, seed, 0.5, False, jax.devices(), peak,
                            time.perf_counter())


def test_weights_have_the_programs_layout():
    from repro.models.model import init_params

    spec = tiny_cell.spec()
    cfg, model = spec["cfg"], spec["model"]
    ours = jax.eval_shape(lambda: model_init.make_params(
        model, cfg, np.zeros(2, np.uint32)))
    theirs = jax.eval_shape(lambda: init_params(
        jax.random.PRNGKey(0), model.program_config(cfg)))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), ours) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), theirs)


def test_weights_come_from_the_seed():
    spec = tiny_cell.spec()
    cfg, model = spec["cfg"], spec["model"]
    a = model_init.make_params(model, cfg, harness.traffic.weight_key(7))
    b = model_init.make_params(model, cfg, harness.traffic.weight_key(7))
    c = model_init.make_params(model, cfg, harness.traffic.weight_key(8))
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(a["embed"], c["embed"])


@pytest.fixture
def copy_with_new_cell(tmp_path):
    """A checkout copy with a config, a mix, limits and a cell added;
    returns (root, digests of every file that was there before)."""
    root = tmp_path / "co"
    shutil.copytree(ROOT / "benchmarks" / "chip", root / "benchmarks" /
                    "chip", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()
              and p.name != "BENCHMARK.json"}
    chip = root / "benchmarks" / "chip"
    shutil.copy(tiny_cell.DATA / "tiny.json", chip / "configs" /
                "tiny.json")
    shutil.copy(tiny_cell.DATA / "tinyvid.json", chip / "traffic" /
                "tinyvid.json")
    shutil.copy(tiny_cell.DATA / "limits.json", chip / "limits" /
                "tiny-cell.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny", "source": "a test-sized decoder",
        "file": "benchmarks/chip/configs/tiny.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny-cell", "config": "tiny", "traffic": "tinyvid",
        "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before


def test_new_cell_is_found_by_name(copy_with_new_cell):
    root, before = copy_with_new_cell
    chip = root / "benchmarks" / "chip"
    spec = harness.load_cell("tiny-cell", root=root, here=chip)
    assert spec["cfg"]["d_model"] == 64
    assert spec["mix"]["name"] == "tinyvid"
    assert set(spec["limits"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert [m["name"] for m in spec["end_to_end"]] == ["tokens_per_s",
                                                       "setup_s"]
    for m in spec["per_layer"] + spec["end_to_end"]:
        assert callable(harness.reader(chip, m["name"]))
    assert all(p.read_bytes() == b for p, b in before.items())


def _cli(root, cell, env_extra):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("XLA_FLAGS")}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, str(root / "benchmarks" / "chip" / "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_result_without_a_chip(copy_with_new_cell):
    root, _ = copy_with_new_cell
    shutil.copytree(ROOT / "src", root / "src",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(root, "tiny-cell", {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_no_result_without_the_program(copy_with_new_cell):
    root, _ = copy_with_new_cell        # BENCHMARK.json + paths only
    p = _cli(root, "tiny-cell", {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_the_tiny_cell_is_correct():
    res = _run(tiny_cell.spec())
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}


def test_state_left_unchanged_is_not_correct(monkeypatch):
    from repro.api.engine import Engine
    execute = Engine.execute
    monkeypatch.setattr(Engine, "execute", lambda self, plan, data, **kw:
                        execute(self, plan, data, update=False))
    res = _run(tiny_cell.spec())
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    from repro.core.executor import DHPExecutor
    group_batch = DHPExecutor._group_batch

    def half(self, seqs, degree, spans=None):
        return group_batch(self, seqs[::2], degree,
                           spans=None if spans is None else spans[::2])

    monkeypatch.setattr(DHPExecutor, "_group_batch", half)
    res = _run(tiny_cell.spec())
    assert not res["correct"], res["checks"]
