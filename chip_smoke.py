#!/usr/bin/env python3
"""Smoke run of DHP training on TPU, through the entry points a user calls.

    python chip_smoke.py             # one chip: train + kernel checks
    python chip_smoke.py --chips 4   # 2x2 host: concurrent CP rings vs static

One chip: internvl3-2b at its published widths (bf16 parameters, remat),
cut in depth only, trains a few steps through `ClusterSpec` ->
`Engine.train` -> `Strategy.plan` -> `DHPExecutor.run_plan` -> the jitted
AdamW update on OpenVid-shaped packed batches. The step-0 loss is checked
against a float32 plain forward of the same parameters on the same
packed batch, and both Pallas attention kernels run compiled at the
model's head layout against their jnp oracles.

Four chips: the same model planned by `dhp` over four ranks on a batch
whose longest sequences exceed the per-rank budget, so context-parallel
rings of unequal degree run side by side; loss and gradient norm must
match the `static` plan of the same batch on the same chips.

Exits non-zero, printing no result, unless JAX sees a TPU. The last line
of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.api import ClusterSpec, Engine, get_strategy  # noqa: E402
from repro.api.cli import use_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.group_pool import multiple_bucket  # noqa: E402
from repro.core.packing import flatten_group  # noqa: E402
from repro.data.pipeline import HeterogeneousLoader  # noqa: E402
from repro.kernels import interpret_mode, ops  # noqa: E402
from repro.models.model import forward, init_params  # noqa: E402
from repro.training.optimizer import global_norm  # noqa: E402

ARCH = "internvl3-2b"
#: of the published 28 layers. 4 layers are 653 M parameters; with fp32
#: AdamW moments that is ~6.1 GiB of state, and the 4096-token grad
#: step adds ~4.8 GiB of temporaries, which leaves room on a 16 GB chip.
#: The whole model (1.78 B parameters, ~16 B each) cannot train on one.
DEPTH = 4
SEED = 0

# one chip: every group is degree 1, so one rank must hold the longest
# sequence: max_tokens = mem_budget (tokens per rank) = the packed bucket
BUCKET = 4096
STEPS = 5
GLOBAL_BATCH = 8

# four chips: sequences up to 3x the per-rank budget force CP degree >= 2;
# this seed's batch plans a degree-3 ring beside a degree-1 group
RING_BUDGET = 2048
RING_MAX_TOKENS = 3 * RING_BUDGET
RING_BATCH = 4
RING_SEED = 6

#: step-0 loss vs the fp32 reference: the trained step runs bf16
#: parameters and activations (8 mantissa bits, 2^-9 relative rounding
#: per op) with fp32 softmax and norms; averaged over ~10^4 tokens the
#: mean NLL moves by well under 0.5% of its ~12 nats
LOSS_RTOL = 5e-3
#: kernel outputs are bf16: half an ulp of |o| <= 4 is 2^-7 = 0.0078,
#: and the in-kernel fp32 softmax adds less than that
KERNEL_ATOL = 3e-2
#: dhp vs static on one batch: the same tokens and parameters, grouped
#: differently. Each group's gradient comes out of its grad step in bf16
#: (2^-9 relative) before the token-weighted fp32 sum, and different
#: groupings round different partial sums
RING_LOSS_RTOL = 2e-3
RING_GNORM_RTOL = 1e-2

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAIL: {what}")


class CompileClock:
    """Seconds XLA spent compiling since the process started."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration


def model_config():
    cfg = get_config(ARCH)
    cut = cfg.with_(n_layers=DEPTH)
    print(f"model {ARCH}: {DEPTH} of {cfg.n_layers} layers (depth cut "
          f"only); d_model={cut.d_model} heads={cut.n_heads}/"
          f"{cut.kv_heads} head_dim={cut.resolved_head_dim} "
          f"d_ff={cut.d_ff} vocab={cut.vocab} params={cut.param_dtype} "
          f"remat={cut.remat}", flush=True)
    return cut


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


# --------------------------------------------------------------------------
# one chip: train, then check step 0 against an fp32 reference
# --------------------------------------------------------------------------
def train_one_chip(cfg, device, clock: CompileClock):
    """Train, check the losses, and return step 0's packed group buffers."""
    cluster = ClusterSpec(devices=[device], mem_budget=float(BUCKET),
                          bucketing=partial(multiple_bucket,
                                            multiple=BUCKET))
    engine = Engine(cfg, cluster, strategy="dhp", seed=SEED)
    n_params = sum(x.size for x in jax.tree.leaves(engine.state.params))
    print(f"params={n_params} bucket={BUCKET} steps={STEPS} "
          f"global_batch={GLOBAL_BATCH} dataset=openvid", flush=True)
    loader = HeterogeneousLoader("openvid", GLOBAL_BATCH, engine.cfg.vocab,
                                 seed=SEED, max_tokens=BUCKET)
    start = loader.state()
    plans: list = []
    compile_at_step: list = []
    t0 = time.perf_counter()
    history = engine.train(
        loader, steps=STEPS, plan_log=plans,
        log=lambda _: compile_at_step.append(clock.seconds))
    wall = time.perf_counter() - t0
    prev = 0.0
    for m, c in zip(history, compile_at_step):
        print(f"step {m.step} loss={m.loss!r} tokens={m.tokens} "
              f"step_time_s={m.step_time_s!r} exe_misses={m.exe_misses} "
              f"compile_s={c - prev!r} degrees={m.degree_histogram} "
              f"padding_eff={m.padding_efficiency!r}", flush=True)
        prev = c
    print(f"train wall_s={wall!r} peak_bytes_in_use={peak_bytes([device])[0]}",
          flush=True)
    check(len(history) == STEPS, f"{len(history)} of {STEPS} steps ran")
    check(all(math.isfinite(m.loss) for m in history), "non-finite loss")

    loader.set_state(start)
    batches = packed_groups(engine, plans[0], next(loader))
    loss0 = history[0].loss
    engine.state = None              # free the train state for the reference
    engine.close()
    ref = reference_loss(engine, batches)
    rel = abs(loss0 - ref) / abs(ref)
    print(f"step0 loss={loss0!r} fp32_reference={ref!r} rel_diff={rel!r} "
          f"rtol={LOSS_RTOL}", flush=True)
    check(rel <= LOSS_RTOL, "step-0 loss disagrees with the fp32 reference")
    return batches


def packed_groups(engine, plan, data) -> list:
    """The packed buffers the executor built for `plan`'s groups."""
    spans = data.spans_by_id()
    out = []
    for mb in plan.micro_batches:
        for g in mb.groups:
            seqs = [data.by_id(i) for i in g.seq_ids]
            bucket = engine.cluster.pool().bucket(sum(len(s) for s in seqs))
            batch, _ = flatten_group(
                seqs, bucket, spans=[spans.get(i) for i in g.seq_ids])
            out.append(batch)
    return out


def reference_loss(engine, batches) -> float:
    """Token-weighted mean next-token NLL over the packed `batches`, from
    a plain float32 forward (reference attention, full-precision matmuls)
    of the step-0 parameters, which the engine drew from its seed."""
    cfg = engine.cfg.with_(attn_impl="reference", remat=False)
    params = init_params(jax.random.PRNGKey(engine.seed), engine.cfg)

    @jax.jit
    def nll(params, batch):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        logits, _ = forward(p32, cfg, batch)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, batch["labels"][..., None],
                                   axis=-1)[..., 0]
        w = batch["loss_mask"] if "loss_mask" in batch else batch["mask"]
        return jnp.sum((logz - gold) * w), jnp.sum(w)

    total = count = 0.0
    with jax.default_matmul_precision("highest"):
        for batch in batches:
            s, c = nll(params, batch)
            total += float(s)
            count += float(c)
    return total / count


# --------------------------------------------------------------------------
# one chip: the Pallas attention kernels, compiled, against kernels/ref.py
# --------------------------------------------------------------------------
def assert_compiled(fn, *args, **kw) -> None:
    check(not interpret_mode(), "Pallas would run in interpret mode")
    hlo = fn.lower(*args, **kw).compile().as_text()
    check("tpu_custom_call" in hlo, f"{fn.__name__}: no Mosaic kernel")


def kernel_checks(cfg, batch) -> None:
    """Model head layout on a real packed buffer: `batch` is one
    flatten_group output ([1, S] segment and modality tables)."""
    S = batch["segment_ids"].shape[1]
    H, Hkv, D = cfg.n_heads, cfg.kv_heads, cfg.resolved_head_dim
    key = jax.random.PRNGKey(SEED + 7)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, S, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (1, S, Hkv, D), jnp.bfloat16)
    v = jax.random.normal(kv, (1, S, Hkv, D), jnp.bfloat16)
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    seg = jnp.asarray(batch["segment_ids"])
    span = jnp.asarray(batch["modality_ids"])
    cases = [
        ("flash_attention", ops.flash_attention, (), {}),
        ("flash_attention_packed", ops.flash_attention_packed, (seg,), {}),
        ("flash_attention_packed+spans", ops.flash_attention_packed,
         (seg,), {"span_ids": span}),
    ]
    for name, fn, extra, kw in cases:
        assert_compiled(fn, q, k, v, *extra, mode="causal", **kw)
        out = fn(q, k, v, *extra, mode="causal", **kw)
        with jax.default_matmul_precision("highest"):
            ref = fn(*f32, *extra, mode="causal", ref=True, **kw)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
        print(f"kernel {name} S={S} heads={H}/{Hkv} head_dim={D} "
              f"max_abs_err={err!r} atol={KERNEL_ATOL}", flush=True)
        check(math.isfinite(err) and err <= KERNEL_ATOL,
              f"{name} disagrees with kernels/ref.py")


# --------------------------------------------------------------------------
# four chips: concurrent CP rings of unequal degree vs the static plan
# --------------------------------------------------------------------------
def ring_phase(cfg, devices) -> None:
    cluster = ClusterSpec(devices=list(devices),
                          mem_budget=float(RING_BUDGET),
                          bucketing=partial(multiple_bucket,
                                            multiple=RING_BUDGET))
    engine = Engine(cfg, cluster, strategy="dhp", seed=SEED)
    data = next(HeterogeneousLoader("openvid", RING_BATCH,
                                    engine.cfg.vocab, seed=RING_SEED,
                                    max_tokens=RING_MAX_TOKENS))
    static = get_strategy("static").bind(
        engine.cost_model, cluster.n_replicas, cluster.mem_budget)
    plan_d, plan_s = engine.plan(data), static.plan(data.infos)
    print(f"ranks={cluster.n_replicas} budget={RING_BUDGET} tokens/rank "
          f"lengths={[s.length for s in data.infos]}", flush=True)
    for name, plan in (("dhp", plan_d), ("static", plan_s)):
        print(f"{name} degree_histogram={plan.degree_histogram} groups="
              f"{[[(g.degree, g.tokens) for g in mb.groups] for mb in plan.micro_batches]}",
              flush=True)
    check(max(plan_d.degree_histogram) >= 2, "dhp formed no CP ring")

    params = engine.state.params
    results = {}
    for name, plan in (("dhp", plan_d), ("static", plan_s)):
        t0 = time.perf_counter()
        loss, grads = engine.executor.run_plan(params, plan, data)
        gnorm = float(global_norm(grads))
        results[name] = (float(loss), gnorm)
        print(f"{name} loss={float(loss)!r} grad_norm={gnorm!r} "
              f"wall_s={time.perf_counter() - t0!r} "
              f"exe_misses={engine.executor.last_run_stats['exe_misses']} "
              f"grad_d2d_bytes="
              f"{engine.executor.last_run_stats['grad_d2d_bytes']}",
              flush=True)
        del grads
    leaf = jax.tree.leaves(params)[0]
    print(f"placement: params on {sorted(d.id for d in leaf.devices())} "
          f"committed={leaf.committed}; peak_bytes_in_use per chip="
          f"{peak_bytes(devices)}", flush=True)
    (ld, gd), (ls, gs) = results["dhp"], results["static"]
    check(all(map(math.isfinite, (ld, gd, ls, gs))), "non-finite result")
    check(abs(ld - ls) <= RING_LOSS_RTOL * abs(ls),
          f"loss dhp {ld} vs static {ls}")
    check(abs(gd - gs) <= RING_GNORM_RTOL * gs,
          f"grad norm dhp {gd} vs static {gs}")
    print(f"dhp_vs_static loss_rel={abs(ld - ls) / abs(ls)!r} "
          f"grad_norm_rel={abs(gd - gs) / gs!r}", flush=True)

    m = engine.execute(plan_d, data)        # the full step, update included
    print(f"dhp train step loss={m.loss!r} step_time_s={m.step_time_s!r}",
          flush=True)
    check(math.isfinite(m.loss) and abs(m.loss - ld) <= RING_LOSS_RTOL * ld,
          "train step loss differs from its own grad pass")
    engine.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the CP-ring phase on a 2x2 host")
    args = ap.parse_args(argv)
    use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU visible to JAX", file=sys.stderr)
        return 1
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(devices)}")
    clock = CompileClock()
    cfg = model_config()
    t0 = time.perf_counter()
    if args.chips == 4:
        ring_phase(cfg, devices[:4])
    else:
        kernel_checks(cfg, train_one_chip(cfg, dev, clock)[0])
    print(f"total_s={time.perf_counter() - t0!r} "
          f"compile_s={clock.seconds!r}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
