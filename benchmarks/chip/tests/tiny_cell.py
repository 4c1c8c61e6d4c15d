"""The test-sized cell: a tiny dense decoder on a tiny OpenVid-like mix,
with the limits it was checked against (`data/tiny/`)."""
import json
from pathlib import Path

import harness
import traffic

DATA = Path(__file__).resolve().parent / "data" / "tiny"


def spec(limits=None):
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((DATA / "tiny.json").read_text())
    return {
        "cell": {"name": "tiny-cell", "config": "tiny",
                 "traffic": "tinyvid", "chips": 1},
        "cfg": cfg,
        "model": harness.model_of(cfg),
        "mix": traffic.load(DATA / "tinyvid.json"),
        "limits": limits or json.loads(
            (DATA / "limits.json").read_text())["limits"],
        "end_to_end": bench["end_to_end"],
        "per_layer": bench["per_layer"],
        "here": harness.HERE,
    }
