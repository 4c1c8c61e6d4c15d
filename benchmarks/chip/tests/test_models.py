"""Model modules (`models/<model>.py`) and what a metric reader is handed.

* the dense GQA module gives the weights, the reference's readings and
  the model FLOPs that the code it was moved from gave (golden values);
* a new architecture joins as new files only: a model module, a
  configuration, a mix, limits and a cell, with no file that was there
  before changed;
* a reader added as a new file reads a program counter, an entry of the
  executor's `last_run_stats` and a device op's time from `run`;
* `roofline.share` on numbers worked by hand.
"""
import hashlib
import json
import shutil
import time
from pathlib import Path

import jax
import numpy as np
import pytest

import flops
import harness
import model_init
import reference
import roofline
import tiny_cell
import trace_reduce
import traffic

ROOT = harness.ROOT
TRACE = Path(__file__).resolve().parent / "data" / "trace"

# -- golden values: recorded from the code before it moved into
# -- models/dense_gqa.py (CPU, tiny config, seed 7; openvid, seed 0)
WEIGHTS_SHA256 = ("3568269754cabd4b967dec4822d4376d"
                  "2f46a3eba5540d00aa0d4faa69015836")
REF = {
    "losses": [6.787578490045336, 6.683229901573875, 6.874151509602864],
    "grad_norms": [
        14.928569793701172, 0.9479286670684814, 1.399767279624939,
        1.8416239023208618, 1.4392023086547852, 1.7896157503128052,
        0.2767834961414337, 0.21255601942539215, 1.8261348009109497,
        1.3845454454421997, 1.2710193395614624, 0.1742783933877945],
    "change_norms": [
        0.10109055042266846, 0.10620121657848358, 0.03724401071667671,
        0.05192128196358681, 0.05233694240450859, 0.0367719829082489,
        0.0, 0.0, 0.0788191631436348, 0.07256240397691727,
        0.07356607168912888, 0.0],
}
#: float32 sums in another order (the CPU's thread count) move the last
#: digits: 1.5e-7 relative between one thread and many; a change to the
#: model's arithmetic moves them by far more
REF_RTOL = 1e-5
OPENVID_STEP_FLOPS = [43025414823936, 43075776208896, 42374390611968]


def _digest(tree) -> str:
    h = hashlib.sha256()
    for name, leaf in zip(model_init.leaf_names(tree), jax.tree.leaves(tree)):
        a = np.asarray(leaf)
        h.update(f"{name}:{a.dtype}:{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_golden_weights():
    spec = tiny_cell.spec()
    p = model_init.make_params(spec["model"], spec["cfg"],
                               traffic.weight_key(7))
    assert _digest(p) == WEIGHTS_SHA256


def test_golden_reference():
    spec = tiny_cell.spec()
    cfg, mix = spec["cfg"], spec["mix"]
    steps = [traffic.step(mix, cfg["vocab"], 7, k) for k in range(3)]
    ref = reference.run(spec["model"], cfg, mix, traffic.weight_key(7),
                        steps)
    for key, want in REF.items():
        np.testing.assert_allclose(ref[key], want, rtol=REF_RTOL,
                                   err_msg=key)


def test_golden_flops():
    cfg = json.loads((harness.HERE / "configs" /
                      "internvl3-2b-d4.json").read_text())
    mix = traffic.load(harness.HERE / "traffic" / "openvid.json")
    model = harness.model_of(cfg)
    assert [flops.step_flops(model, cfg,
                             traffic.step(mix, cfg["vocab"], 0, k)[0])
            for k in range(3)] == OPENVID_STEP_FLOPS


def test_a_module_sets_the_rule_of_its_own_leaf():
    import types

    import jax.numpy as jnp

    model = types.ModuleType("router_model")
    model.shapes = lambda cfg: {"router": {"bias": (3, 4)},
                                "w": (4, cfg["d"])}
    model.INIT = {"router/bias": lambda key, shape, dtype:
                  jnp.full(shape, 0.5, dtype)}
    p = model_init.make_params(model, {"d": 8, "param_dtype": "float32"},
                               traffic.weight_key(7))
    assert np.all(np.asarray(p["router"]["bias"]) == 0.5)
    assert 0.3 < np.std(np.asarray(p["w"])) < 0.7     # 1/sqrt(fan_in 4)


@pytest.mark.parametrize("model", [None, "no_such_model"])
def test_a_config_must_name_a_known_model(model):
    cfg = {"name": "x", **({"model": model} if model else {})}
    with pytest.raises(SystemExit, match="dense_gqa"):
        harness.model_of(cfg)


# --------------------------------------------------------------------------
# a new architecture as new files only
# --------------------------------------------------------------------------
TIED_MODULE = '''"""A dense GQA decoder whose LM head is the embedding, transposed
(the program's `tie_embeddings`)."""
from models import dense_gqa

program_config = dense_gqa.program_config
token_flops = dense_gqa.token_flops
pair_flops = dense_gqa.pair_flops


def shapes(cfg):
    s = dense_gqa.shapes(cfg)
    del s["head"]
    return s


def row_loss(p, row, cfg, mm):
    return dense_gqa.row_loss({**p, "head": p["embed"].T}, row,
                              {**cfg, "tie_embeddings": False}, mm)
'''


def _files(root: Path):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


@pytest.fixture
def copy_with_new_model(tmp_path):
    """A checkout copy with a model module (a tied-head dense decoder), a
    configuration that names it, a mix, limits and a cell added; returns
    (root, digests of every file that was there before)."""
    root = tmp_path / "co"
    shutil.copytree(ROOT / "benchmarks" / "chip", root / "benchmarks" /
                    "chip", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: d for p, d in _files(root).items()
              if p.name != "BENCHMARK.json"}
    chip = root / "benchmarks" / "chip"
    (chip / "models" / "tied_dense.py").write_text(TIED_MODULE)
    cfg = json.loads((tiny_cell.DATA / "tiny.json").read_text())
    cfg.update(name="tiny-tied", model="tied_dense", tie_embeddings=True)
    (chip / "configs" / "tiny-tied.json").write_text(json.dumps(cfg))
    shutil.copy(tiny_cell.DATA / "tinyvid.json", chip / "traffic" /
                "tinyvid.json")
    shutil.copy(tiny_cell.DATA / "limits.json", chip / "limits" /
                "tiny-tied-cell.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-tied", "source": "a test-sized tied-head decoder",
        "file": "benchmarks/chip/configs/tiny-tied.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny-tied-cell", "config": "tiny-tied",
        "traffic": "tinyvid", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before


def _load(root):
    return harness.load_cell("tiny-tied-cell", root=root,
                             here=root / "benchmarks" / "chip")


def test_new_model_is_found_by_name(copy_with_new_model):
    root, before = copy_with_new_model
    spec = _load(root)
    assert spec["cfg"]["model"] == "tied_dense"
    assert Path(spec["model"].__file__).parent == (
        root / "benchmarks" / "chip" / "models")
    assert "head" not in spec["model"].shapes(spec["cfg"])
    assert all(_files(root)[p] == d for p, d in before.items())


def test_new_model_weights_have_the_programs_layout(copy_with_new_model):
    from repro.models.model import init_params

    spec = _load(copy_with_new_model[0])
    cfg, model = spec["cfg"], spec["model"]
    ours = jax.eval_shape(lambda: model_init.make_params(
        model, cfg, np.zeros(2, np.uint32)))
    theirs = jax.eval_shape(lambda: init_params(
        jax.random.PRNGKey(0), model.program_config(cfg)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), ours) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), theirs)


def test_new_model_cell_is_correct(copy_with_new_model):
    root, before = copy_with_new_model
    res = harness.run_cell(_load(root), 3, 0.5, False, jax.devices(),
                           harness.peak_of("TPU v5 lite"),
                           time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert all(_files(root)[p] == d for p, d in before.items())


# --------------------------------------------------------------------------
# what a reader is handed
# --------------------------------------------------------------------------
READERS = {
    # a program counter: the last sample of a counter track
    "queue_last": '''
def read(run):
    samples = [c for c in run["counters"] if c["name"] == "queue_depth"]
    return float(samples[-1]["values"]["waiting"]) if samples else None
''',
    # an entry of the executor's last_run_stats, summed over the steps
    "groups_run": '''
def read(run):
    steps = run["steps"]
    if not steps or "groups" not in steps[0]:
        return None
    return float(sum(s["groups"] for s in steps))
''',
    # a device op's self time a step, by op name prefix
    "dus_ms": '''
def read(run):
    dev = run["device"]
    ops = [s for n, s in (dev["ops"] if dev else {}).items()
           if n.startswith("dynamic-update-slice")]
    return 1e3 * sum(ops) / len(run["steps"]) if ops else None
''',
}


@pytest.fixture
def readers(tmp_path):
    """The readers of `READERS`, each a new file under `metrics/` of a
    copy of the benchmark."""
    chip = tmp_path / "chip"
    shutil.copytree(harness.HERE / "metrics", chip / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name, src in READERS.items():
        (chip / "metrics" / f"{name}.py").write_text(src)
    return {name: harness.reader(chip, name) for name in READERS}


def test_readers_on_a_synthetic_run(readers):
    from repro.obs import Tracer

    tracer = Tracer()
    t = time.perf_counter()
    tracer.complete(trace_reduce.ANCHOR, t, 0.0)
    tracer.complete("collect", t + 0.1, 0.25)
    tracer.counter("queue_depth", {"waiting": 3})
    tracer.counter("queue_depth", {"waiting": 5})
    spans, counters = harness.program_events(
        tracer.to_json()["traceEvents"], t)
    assert [s["name"] for s in spans] == ["collect"]
    assert spans[0]["start_s"] == pytest.approx(t + 0.1, abs=1e-5)
    assert spans[0]["dur_s"] == pytest.approx(0.25)
    assert [c["values"] for c in counters] == [{"waiting": 3},
                                               {"waiting": 5}]
    assert all(c["start_s"] >= t for c in counters)
    run = {"steps": [{"groups": 4, "real_tokens": 8},
                     {"groups": 5, "real_tokens": 8}],
           "spans": spans, "counters": counters,
           "device": {"ops": {"dynamic-update-slice.7": 0.08,
                              "dynamic-update-slice.6": 0.04,
                              "fusion.1": 1.0}}}
    assert readers["queue_last"](run) == 5.0
    assert readers["groups_run"](run) == 9.0
    assert readers["dus_ms"](run) == pytest.approx(60.0)
    empty = {"steps": [{}], "spans": [], "counters": [], "device": None}
    assert all(r(empty) is None for r in readers.values())


def test_readers_on_the_recorded_trace(readers):
    run = json.loads((TRACE / "run.json").read_text())
    run["device"] = trace_reduce.reduce(
        trace_reduce.load(TRACE / "trace.xplane.pb.gz"), run["chips"],
        run["host_spans"])
    run["counters"] = []        # the training program samples none
    ops = run["device"]["ops"]
    assert run["device"]["device_ops"] == [
        [n, s] for n, s in sorted(ops.items(), key=lambda kv: -kv[1])[:10]]
    assert len(ops) > 10
    dus = sum(s for n, s in ops.items()
              if n.startswith("dynamic-update-slice"))
    assert dus > 0
    assert readers["dus_ms"](run) == pytest.approx(
        1e3 * dus / len(run["steps"]))
    assert readers["queue_last"](run) is None
    # the recorded run predates rows with every entry of last_run_stats:
    # no `groups` there; its one step ran six groups
    assert readers["groups_run"](run) is None
    run["steps"][0]["groups"] = 6
    assert readers["groups_run"](run) == 6.0


def test_steps_carry_every_number_of_last_run_stats(monkeypatch):
    """The tiny cell on the CPU: each window step's row holds the
    executor's numeric `last_run_stats`, and `run` the configuration and
    the window's sequences."""
    seen = []
    monkeypatch.setattr(harness, "reader", lambda here, name: seen.append)
    spec = tiny_cell.spec()
    res = harness.run_cell(spec, 5, 0.5, False, jax.devices(),
                           harness.peak_of("TPU v5 lite"),
                           time.perf_counter())
    run = seen[0]
    assert run["cfg"] == spec["cfg"]
    assert len(run["seqs"]) == len(run["steps"]) == res["attempted"]
    assert run["seqs"][0] == traffic.step(spec["mix"], spec["cfg"]["vocab"],
                                          5, 1)[0]
    for row in run["steps"]:
        for key in ("groups", "grad_d2d_bytes", "padding_efficiency",
                    "real_tokens", "padded_tokens", "exe_misses",
                    "wait_ms", "schedule_ms", "step_time_s"):
            assert key in row, key
    assert run["counters"] == [] and run["device"] is None


# --------------------------------------------------------------------------
# the roofline
# --------------------------------------------------------------------------
PEAK = {"bf16_flops": 200e12, "hbm_bytes_per_s": 800e9}


@pytest.mark.parametrize("flops_, nbytes, seconds, want", [
    (100e12, 1e9, 1.0, 0.5),        # compute bound: 0.5 s of 1 s
    (1e12, 400e9, 1.0, 0.5),        # memory bound: 0.5 s of 1 s
    (200e12, 800e9, 2.0, 0.5),      # both bounds equal
    (2e9, 4e6, 20e-6, 0.5),         # a 20 us kernel, 10 us of FLOPs
])
def test_roofline_share(flops_, nbytes, seconds, want):
    assert roofline.share(flops_, nbytes, seconds, PEAK) == pytest.approx(
        want)


def test_roofline_needs_a_time():
    with pytest.raises(ValueError):
        roofline.share(1e12, 1e9, 0.0, PEAK)
