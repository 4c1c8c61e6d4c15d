"""The reader of `grad_accum_ms`, on hand-written runs and on the
recorded run of a program that sums the gradients on the host."""
import json
from pathlib import Path

import pytest

import harness
import trace_reduce

DATA = Path(__file__).resolve().parent / "data" / "trace"


def _read(run):
    return harness.reader(harness.HERE, "grad_accum_ms")(run)


def _run(programs, steps=4):
    return {"steps": [{}] * steps, "spans": [],
            "device": {"programs": programs}}


def test_device_ms_per_step():
    run = _run({"fwd_bwd": 4.0, "accumulate_grads": 0.18,
                "apply_update": 0.1})
    assert _read(run) == pytest.approx(45.0)


def test_none_without_a_trace():
    assert _read({"steps": [{}] * 2, "spans": [], "device": None}) is None


def test_none_without_the_program():
    assert _read(_run({"fwd_bwd": 4.0, "apply_update": 0.1})) is None
    # the recorded trace's program summed the gradients on the host
    run = json.loads((DATA / "run.json").read_text())
    run["device"] = trace_reduce.reduce(
        trace_reduce.load(DATA / "trace.xplane.pb.gz"), run["chips"],
        run["host_spans"])
    assert run["device"]["programs"]["fwd_bwd"] > 0
    assert _read(run) is None


def test_listed_in_the_benchmark():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    m = {m["name"]: m for m in bench["per_layer"]}["grad_accum_ms"]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
            m["workloads"]) == ("ms", "lower", "device_trace",
                                "executor host side", "tokens_per_s",
                                ["ivl2b-openvid-1c"])
