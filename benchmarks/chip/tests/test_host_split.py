"""The reader of the executor's `collect.sum` spans (`grad_host_sum_s`),
on synthetic runs and on the recorded run of a program from before the
executor split `collect` into spans of its parts."""
import json
from pathlib import Path

import pytest

import harness

SPANS = {"grad_host_sum_s": "collect.sum"}
#: the other spans a split `collect` has held (waiting on the device,
#: gradients to the host, the mean back to the device)
OTHERS = ("collect.wait", "collect.d2h", "grad_upload")
DATA = Path(__file__).resolve().parent / "data" / "trace"


def _run(spans, steps=2):
    return {"steps": [{}] * steps,
            "spans": [{"name": n, "start_s": 0.0, "dur_s": d}
                      for n, d in spans]}


@pytest.mark.parametrize("metric", sorted(SPANS))
def test_sums_its_spans_per_step(metric):
    span = SPANS[metric]
    others = [(n, 9.0) for n in OTHERS]
    run = _run([("collect", 20.0), (span, 1.5), (span, 2.5),
                (span, 0.5)] + others)
    assert harness.reader(harness.HERE, metric)(run) == pytest.approx(2.25)


@pytest.mark.parametrize("metric", sorted(SPANS))
def test_zero_when_the_split_lacks_its_span(metric):
    others = [(n, 9.0) for n in OTHERS]
    run = _run([("collect", 30.0)] + others)
    assert harness.reader(harness.HERE, metric)(run) == 0.0


@pytest.mark.parametrize("metric", sorted(SPANS))
def test_none_without_spans(metric):
    assert harness.reader(harness.HERE, metric)(_run([])) is None


@pytest.mark.parametrize("metric", sorted(SPANS))
def test_none_for_a_program_without_the_split(metric):
    # the recorded run: `collect` spans, none of the split inside them
    run = json.loads((DATA / "run.json").read_text())
    assert any(s["name"] == "collect" for s in run["spans"])
    assert harness.reader(harness.HERE, metric)(run) is None


def test_listed_in_the_benchmark():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    listed = {m["name"]: m for m in bench["per_layer"]}
    for metric in SPANS:
        m = listed[metric]
        assert (m["source"], m["layer"], m["moves"], m["workloads"]) == (
            "program_span", "executor host side", "tokens_per_s",
            ["ivl2b-openvid-1c"])
        assert (m["unit"], m["better"]) == ("s", "lower")
    for pruned in ("grad_wait_s", "grad_d2h_s", "grad_upload_s"):
        assert pruned not in listed
        assert not (harness.HERE / "metrics" / f"{pruned}.py").exists()
