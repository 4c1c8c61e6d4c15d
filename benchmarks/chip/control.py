#!/usr/bin/env python3
"""Readings that set the limits of `correct`: the control and the faults.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 11 12 13

Not part of a benchmark run. For each seed, at the cell's own sizes and
steps, on the chip, each reading is judged against the cell's limits
(`limits/<cell>.json`) by the benchmark's own `checks.judge`:

* `control`: the reference computed in float8 (`reference.py`,
  precision "fp8") in the program's place, against the float32
  reference: what a lower-precision step would read;
* `half_batch`: the float32 reference trained on every other sequence
  of each step, the mean over those, against the whole batch;
* `state_unchanged`: a step that returns its state unchanged leaves the
  optimizer's moments and the parameters where they were, so its first
  gradient and its change read 0 on every leaf; it is not run.

One JSON line per seed on standard output, each reading with its
numbers, their limits and `correct`.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import checks  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402


def readings(spec, seed, device):
    cfg, mix = spec["cfg"], spec["mix"]
    key = traffic.weight_key(seed)
    steps = [traffic.step(mix, cfg["vocab"], seed, k)
             for k in range(harness.CHECKED_STEPS)]
    half = [(s[::2], t[::2]) for s, t in steps]
    t0 = time.perf_counter()
    model = spec["model"]
    ref = reference.run(model, cfg, mix, key, steps, device=device)
    out = {"reference_s": time.perf_counter() - t0}
    zeros = [0.0] * len(ref["leaves"])
    runs = {
        "control": reference.run(model, cfg, mix, key, steps,
                                 precision="fp8", device=device),
        "half_batch": reference.run(model, cfg, mix, key, half,
                                    device=device),
        "state_unchanged": {**ref, "grad_norms": zeros,
                            "change_norms": zeros},
    }
    for name, prog in runs.items():
        numbers = checks.gaps(prog, ref)
        correct = checks.judge(numbers, spec["limits"])
        out[name] = {"correct": correct, "checks": numbers}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    spec = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    harness.use_compile_cache()
    try:
        devices = harness.chips_for(spec["cell"])
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    for seed in args.seeds:
        r = readings(spec, seed, devices[0])
        print(json.dumps({"workload": args.workload, "seed": seed, **r}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
