"""The benchmark harness: one cell of `BENCHMARK.json`, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or metric is
a file of its own, found by name:

    BENCHMARK.json          cells, metrics, configuration files
    configs/<config>.json   sizes as run, the published ones, the cut,
                            and `"model"`: the model module it names
    models/<model>.py       the model: `program_config`, `shapes`,
                            `row_loss`, `token_flops`, `pair_flops`
                            (one may build on another: `from models
                            import dense_gqa`)
    traffic/<mix>.json      dataset profile, step budget, planner knobs
    limits/<cell>.json      the limit of each number `correct` compares
    metrics/<metric>.py     `read(run) -> float | None`, one per metric
    roofline.py             `share`: a kernel's share of its roofline
    peaks.json              peak FLOP/s and bytes/s per `device_kind`

A run, in one process:

1. set-up: the weights from the seed, made on the device; the program's
   `Engine` with its lookahead planner; one training step (step 0)
   through `Engine.train`, which compiles every program the cell uses.
   The step's loss and the first gradient (from the optimizer's first
   moment) are read for the check.
2. the window: steps 1, 2, ... through the same `Engine.train` and feed,
   fetched until `--seconds` have passed (whole steps; at least two).
   With `--trace 1` the window runs under the profiler and the
   program's own tracer. The check follows steps 0-2: their losses, and
   each leaf's change after step 2.
3. after the window: the device's peak memory is read, the program's
   state is freed, and the plain float32 reference (`reference.py`,
   with the model module's `row_loss`) trains the same weights through
   the same steps 0-2.
4. the last line of standard output: `correct`, `attempted`, `failed`,
   `metrics`, `device`, with `--trace 1` `breakdown`, and `checks`
   (each compared number beside its limit), which are also the last
   lines on standard error.

What a metric reader is handed (`run`): `cfg`, the configuration;
`seqs`, each window step's sequences (`traffic.Seq`); `steps`, one row a
window step with the step's `StepMetrics` and every int or float entry
of the executor's `last_run_stats`; `spans` and `counters`, the
program's tracer events (`ph: "X"` and `ph: "C"`) on the host clock;
`device`, the reduced profiler trace (`trace_reduce.reduce`: busy time,
`programs`, every op's self time in `ops`); `peak`, the chip's row of
`peaks.json`; and the window's time, tokens and model FLOPs. `spans`
and `counters` are empty and `device` is None unless `--trace 1`.

Without an accelerator, or with fewer chips than the cell asks for, it
exits with code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from functools import lru_cache, partial
from numbers import Real
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

import checks
import flops
import model_init
import reference
import traffic
import trace_reduce

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
#: steps the reference follows: the set-up step and the window's first two
CHECKED_STEPS = 3


class NoChip(RuntimeError):
    """JAX sees no accelerator, or fewer chips than the cell asks for."""


# --------------------------------------------------------------------------
# finding a cell by name
# --------------------------------------------------------------------------
def load_cell(name: str, root: Path = ROOT, here: Path = HERE) -> Dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / config["file"]).read_text())
    return {
        "cell": cell,
        "cfg": cfg,
        "model": model_of(cfg, here),
        "mix": traffic.load(here / "traffic" / f"{cell['traffic']}.json"),
        "limits": json.loads(
            (here / "limits" / f"{name}.json").read_text())["limits"],
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
        "here": here,
    }


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(here: Path, metric: str):
    return _module(here / "metrics" / f"{metric}.py",
                   f"bench_metric_{metric.replace('.', '_')}").read


@lru_cache(maxsize=None)
def _model(path: Path):
    return _module(path, f"bench_model_{path.stem}")


def model_of(cfg: Dict, here: Path = HERE):
    """The model module the configuration names (`"model"`), loaded
    once from `models/<model>.py`."""
    known = sorted(p.stem for p in (here / "models").glob("*.py"))
    if cfg.get("model") not in known:
        raise SystemExit(f"configuration {cfg.get('name')!r} names model "
                         f"{cfg.get('model')!r}; known: {known}")
    return _model(here / "models" / f"{cfg['model']}.py")


def peak_of(kind: str, here: Path = HERE) -> Dict:
    table = json.loads((here / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"peaks.json; known: {sorted(table)}")
    return table[kind]


# --------------------------------------------------------------------------
# the program's side
# --------------------------------------------------------------------------
def ragged(seqs, toks, modality: str):
    """A step of the generator in the program's feed types."""
    from repro.core.cost_model import MMSequence, ModalitySpan
    from repro.data.pipeline import RaggedBatch
    infos = [MMSequence(spans=tuple(
        ModalitySpan(modality if b else "text", s, n,
                     "bidirectional" if b else "causal")
        for s, n, b in seq), seq_id=i).seq_info
        for i, seq in enumerate(seqs)]
    return RaggedBatch(infos=infos, tokens=list(toks))


def annotate(name: str, fn):
    import jax

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapped


def program_events(events: List[Dict], t_anchor: float):
    """The program tracer's spans (`ph: "X"`: name, `start_s`, `dur_s`)
    and counter samples (`ph: "C"`: name, `start_s`, `values`), on the
    host clock: the tracer's `trace_reduce.ANCHOR` event was recorded at
    `t_anchor`."""
    t0 = t_anchor - next(e["ts"] for e in events
                         if e["name"] == trace_reduce.ANCHOR) * 1e-6
    spans = [{"name": e["name"], "start_s": t0 + e["ts"] * 1e-6,
              "dur_s": e["dur"] * 1e-6}
             for e in events if e.get("ph") == "X"
             and e["name"] != trace_reduce.ANCHOR]
    counters = [{"name": e["name"], "start_s": t0 + e["ts"] * 1e-6,
                 "values": e["args"]}
                for e in events if e.get("ph") == "C"]
    return spans, counters


class Feed:
    """The loader of `Engine.train`: steps `first`, `first + 1`, ... of
    the generator, fetched while fewer than `seconds` have passed since
    `t0` or fewer than `min_steps` were fetched. The loop fetches a step
    while the one before it runs, so the last step starts after
    `seconds` and the window is whole steps."""

    def __init__(self, make, first: int, seconds: float, min_steps: int):
        self.make, self.k = make, first
        self.seconds, self.min_steps = seconds, min_steps
        self.t0 = time.perf_counter()
        self.steps: List = []

    def __iter__(self):
        return self

    def __next__(self):
        if (len(self.steps) >= self.min_steps
                and time.perf_counter() - self.t0 >= self.seconds):
            raise StopIteration
        batch, seqs = self.make(self.k)
        self.steps.append(seqs)
        self.k += 1
        return batch


class Probe:
    """The `log` callback of `Engine.train`: what the program's state
    and counters say after each step (every number in the executor's
    `last_run_stats`, and the planner's wait). After step 0 it reads the
    first gradient (from the optimizer's first moment); after the last
    checked step, if the feed has fetched another, each leaf's change,
    on the device and without waiting for it."""

    def __init__(self, engine, b1: float, change):
        self.engine, self.b1, self.change = engine, b1, change
        self.feed: Optional[Feed] = None
        self.done = 0
        self.rows: List[Dict] = []
        self.first_grad: Optional[List[float]] = None
        self.change_norms = None

    def __call__(self, _summary: str) -> None:
        eng = self.engine
        stats = eng.executor.last_run_stats
        self.rows.append({**{k: v for k, v in stats.items()
                             if isinstance(v, Real)},
                          "wait_ms": eng.strategy.last_wait_ms})
        self.done += 1
        if self.done == 1:
            # first moment after one Adam step is (1 - b1) * gradient
            m = np.asarray(checks.leaf_norms(eng.state.opt.m), np.float64)
            self.first_grad = (m / (1.0 - self.b1)).tolist()
            np.asarray(self.change(eng.state.params))  # compiled here
        elif (self.done == CHECKED_STEPS
              and 1 + len(self.feed.steps) > self.done):
            self.change_norms = self.change(eng.state.params)


def run_cell(spec: Dict, seed: int, seconds: float, trace: bool,
             devices, peak: Dict, t_start: float) -> Dict:
    import jax

    from repro.api import ClusterSpec, Engine
    from repro.core.group_pool import multiple_bucket
    from repro.obs import Tracer, tracing
    from repro.training.optimizer import AdamW
    from repro.training.train_step import TrainState

    cell, cfg, mix, model = (spec[k] for k in ("cell", "cfg", "mix",
                                               "model"))
    chips = cell["chips"]
    used = devices[:chips]
    key = traffic.weight_key(seed)
    modality = mix["profile"]["modality"]

    def make(k):
        seqs, toks = traffic.step(mix, cfg["vocab"], seed, k)
        return ragged(seqs, toks, modality), seqs

    cluster = ClusterSpec(
        devices=used, mem_budget=float(mix["rank_budget_tokens"]),
        bucketing=partial(multiple_bucket,
                          multiple=mix["bucket_multiple"]))
    engine = Engine(model.program_config(cfg), cluster,
                    strategy=mix["strategy"],
                    optimizer=AdamW(**mix["optimizer"]), seed=0)
    params = model_init.make_params(model, cfg, key)
    engine.state = TrainState(params=params,
                              opt=engine.optimizer.init(params))
    del params

    def change(params):
        return checks.diff_norms(params,
                                 model_init.make_params(model, cfg, key))

    # -- set-up: step 0, which compiles, through the window's call --------
    probe = Probe(engine, mix["optimizer"]["b1"], change)
    probe.feed = Feed(make, 0, 0.0, 1)
    setup = engine.train(probe.feed, steps=10 ** 9,
                         lookahead=mix["lookahead"], log=probe)

    # -- the window: steps 1, 2, ... -------------------------------------
    tracer = Tracer() if trace else None
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    host_spans: List = []
    with contextlib.ExitStack() as stack:
        if trace:
            engine.execute = annotate("bench.execute", engine.execute)
            engine.strategy.collect = annotate(
                "bench.plan_wait", engine.strategy.collect)
            make = annotate("bench.data", make)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            stack.callback(jax.profiler.stop_trace)
            stack.enter_context(tracing(tracer))
            with jax.profiler.TraceAnnotation(trace_reduce.ANCHOR):
                t_anchor = time.perf_counter()
            tracer.complete(trace_reduce.ANCHOR, t_anchor, 0.0)
            host_spans.append((trace_reduce.ANCHOR, t_anchor, t_anchor))
            stack.enter_context(
                jax.profiler.TraceAnnotation(trace_reduce.WINDOW))
        feed = probe.feed = Feed(make, 1, seconds, CHECKED_STEPS - 1)
        probe.rows = []
        t_w0 = feed.t0
        window = engine.train(feed, steps=10 ** 9,
                              lookahead=mix["lookahead"], log=probe)
        t_w1 = time.perf_counter()
    setup_s = t_w0 - t_start
    window_s = t_w1 - t_w0
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)
    if len(window) != len(feed.steps):
        raise RuntimeError(f"{len(feed.steps)} steps fed, "
                           f"{len(window)} ran")
    losses = [m.loss for m in setup + window]
    prog = {"losses": losses[:CHECKED_STEPS],
            "grad_norms": probe.first_grad,
            "change_norms": np.asarray(
                probe.change_norms if probe.change_norms is not None
                else change(engine.state.params), np.float64).tolist()}

    # -- free the program's state; the reference runs alone ---------------
    engine.state = None
    engine.close()
    probe.engine = None
    del engine, setup
    gc.collect()
    ref = reference.run(model, cfg, mix, key,
                        [traffic.step(mix, cfg["vocab"], seed, k)
                         for k in range(CHECKED_STEPS)],
                        device=used[0])
    numbers = checks.gaps(prog, ref)
    for i, leaf in enumerate(ref["leaves"]):
        print(f"leaf {leaf} grad {prog['grad_norms'][i]!r} "
              f"{ref['grad_norms'][i]!r} change {prog['change_norms'][i]!r} "
              f"{ref['change_norms'][i]!r}", file=sys.stderr)
    print(f"losses {prog['losses']!r} {ref['losses']!r}", file=sys.stderr)
    failed = sum(not math.isfinite(m.loss) for m in window)
    correct = checks.judge(numbers, spec["limits"]) and failed == 0

    run = {
        "cell": cell["name"], "chips": chips, "peak": peak, "cfg": cfg,
        "setup_s": setup_s, "window_s": window_s,
        "tokens": mix["step_tokens"] * len(window),
        "seqs": feed.steps,
        "model_flops": float(sum(flops.step_flops(model, cfg, s)
                                 for s in feed.steps)),
        "memory_peak_bytes": mem_peak,
        "steps": [{**row, "schedule_ms": m.schedule_ms,
                   "padding_eff": m.padding_efficiency,
                   "exe_misses": m.exe_misses,
                   "step_time_s": m.step_time_s}
                  for m, row in zip(window, probe.rows)],
        "spans": [], "counters": [], "device": None,
    }
    dev = used[0]
    result = {"correct": correct, "attempted": len(window),
              "failed": failed, "metrics": {},
              "device": {"platform": dev.platform,
                         "kind": dev.device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": mem_peak}}
    if trace:
        run["spans"], run["counters"] = program_events(
            tracer.to_json()["traceEvents"], t_anchor)
        host_spans += [(f"program.{s['name']}", s["start_s"],
                        s["start_s"] + s["dur_s"]) for s in run["spans"]]
        xplane = next(Path(trace_dir).rglob("*.xplane.pb"))
        red = trace_reduce.reduce(trace_reduce.load(xplane), chips,
                                  host_spans)
        shutil.rmtree(trace_dir, ignore_errors=True)
        run["device"] = red
        result["device"].update(busy_s=red["busy_s"],
                                window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    chosen = spec["per_layer"] if trace else spec["end_to_end"]
    for m in chosen:
        value = reader(spec["here"], m["name"])(run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}
    result["checks"] = numbers
    return result


# --------------------------------------------------------------------------
# the command
# --------------------------------------------------------------------------
def use_compile_cache() -> None:
    """The program's persistent compile cache (a fixed path inside the
    checkout, or JAX_COMPILATION_CACHE_DIR), every program cached."""
    import jax

    from repro.api.cli import use_compile_cache as program_cache
    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def chips_for(cell: Dict):
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip("JAX sees no accelerator")
    if len(devices) < cell["chips"]:
        raise NoChip(f"the cell needs {cell['chips']} chips, JAX sees "
                     f"{len(devices)}")
    return devices


def report(result: Dict) -> None:
    for name, c in result["checks"].items():
        where = f" leaf={c['leaf']}" if "leaf" in c else ""
        print(f"check {name}={c['value']!r} limit={c['limit']!r}{where}",
              file=sys.stderr)
    print(f"correct={result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="DHP training benchmark: "
                                 "one cell of BENCHMARK.json, one run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    use_compile_cache()
    try:
        devices = chips_for(spec["cell"])
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    peak = peak_of(devices[0].device_kind)
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      devices, peak, t_start)
    report(result)
    return 0
