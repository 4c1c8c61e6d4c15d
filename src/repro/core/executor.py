"""DHP Executor — runs an ExecutionPlan on real devices (§5 workflow (4)).

For each planned CP group the executor:
  1. flattens the group's sequences into ONE packed token buffer
     (`core/packing.flatten_group`): tokens concatenated, positions
     reset per segment, a segment-id table making attention
     block-diagonal, padding only at the TAIL to a pooled bucket
     (multiple of the CP degree so the sequence axis shards),
  2. fetches the group's sub-mesh from the GroupPool (the HCCL-pool
     analogue) and the compiled step from the executable pool,
  3. dispatches a shard_map'd forward/backward with segment-aware
     Ring-CP attention over the `cp` axis.

The packed path is the load-bearing perf fix (MegaScale-Omni /
Cornstarch's varlen lesson applied to this repo): the per-sequence path
pads every sequence of a group to a pow2 bucket (worst case ~2x wasted
FLOPs on the a1(1+eta)|s|^2 term the cost model optimizes) and keys
executables on ("grad", start, degree, n_seqs, bucket) — the compilation
count grows with the product of group shapes seen. Packing collapses the
key to ("pgrad", start, degree, packed_bucket): n_seqs and the
per-sequence bucket disappear from the compilation space entirely.
(`start` must stay: a shard_map executable closes over its sub-mesh's
physical devices, so groups on different replica slices cannot share a
compiled artifact.) Set `packed=False` for the legacy per-sequence path.

Trade-off to know: block-diagonal attention only SKIPS cross-segment
work in the Pallas kernel (pl.when drops dead tiles). The portable
chunked and ring-CP paths this CPU demo compiles compute the full
(sum|s|)^2 score matrix and mask it — up to ~n_seqs x more attention
FLOPs than per-sequence, traded against the padding waste, the smaller
non-attention token count, and the collapsed executable space. On the
TPU target (attn_impl="pallas") the skip is real and packing wins
outright; bench_end_to_end.run_packed reports both step_time and
padding so the trade stays visible.

Groups on disjoint device subsets are dispatched WITHOUT blocking — JAX's
async dispatch executes them concurrently, which is exactly the paper's
concurrent heterogeneous CP groups. Token-count-weighted gradient
averaging across groups reproduces the static single-group gradient
bit-for-bit in expectation (invariant tested in tests/test_parallel.py):
dynamic regrouping changes WHERE sequences run, never the math. The
average is taken on the device, in float32 (`accumulate_grads`), on the
devices that hold the parameters: a group on another sub-mesh sends its
gradients there device to device, and outside measuring mode nothing
waits for the device until the run's modality table is read after the
last dispatch.

This module targets the CPU multi-device demo (model_axis=1, params
replicated). On a TPU pod the same code runs with model_axis=TP and
parameter specs from parallel/sharding.py.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig
from ..data.pipeline import RaggedBatch, padded_batch
from ..models.model import forward
from ..obs.trace import get_tracer
from ..training.optimizer import AdamW
from ..training.train_step import token_nll
from .group_pool import GroupPool
from .packing import MODALITY_CLASSES, flatten_group
from .scheduler import ExecutionPlan

#: families whose attention layers support block-diagonal segment masks;
#: recurrent state (ssm/hybrid) crosses segment boundaries, and
#: vlm/audio batches carry extra modal inputs the flattener doesn't pack.
PACKABLE_FAMILIES = ("dense", "moe")

#: XLA module name of the grad step (`fwd_bwd` in `_build_grad_fn`): the
#: chip benchmark's device-trace readers find the program by this name
GRAD_STEP_MODULE = "jit_fwd_bwd"
#: XLA module name of the on-device gradient accumulation
#: (`accumulate_grads`), found the same way
GRAD_ACCUM_MODULE = "jit_accumulate_grads"


def _add_tables(a, b):
    """The sum of two counting tables, None for none yet; tables by name
    (a dict) add name by name."""
    if a is None or b is None:
        return b if a is None else a
    if isinstance(a, dict):
        return {k: _add_tables(a.get(k), b.get(k)) for k in a.keys() | b}
    return a + b


@partial(jax.jit, donate_argnums=0)
def accumulate_grads(acc, out, w, total=None):
    """One group's share of a run's token-weighted mean, in float32 on
    the device: `acc` + `out` cast to float32 (exact from bf16) and
    times the group's token weight `w`, per leaf of (loss, grads), and
    the counting tables (the modality aux table, or tables by name)
    added unweighted. `acc` None starts the sums (the first group);
    `total` divides the sums after the last group's add. `out` is the
    grad step's (loss, grads[, aux]); the accumulator is (loss, grads,
    aux or None), donated, so it is updated in place."""
    loss, grads = out[0], out[1]
    aux = out[2] if len(out) > 2 else None
    f32 = lambda a: a.astype(jnp.float32) * w
    if acc is None:
        acc = (f32(loss), jax.tree.map(f32, grads), aux)
    else:
        acc = (acc[0] + f32(loss),
               jax.tree.map(lambda a, g: a + f32(g), acc[1], grads),
               _add_tables(acc[2], aux))
    if total is None:
        return acc
    return (acc[0] / total, jax.tree.map(lambda a: a / total, acc[1]),
            acc[2])


def _tree_nbytes(tree) -> int:
    return sum(a.nbytes for a in jax.tree.leaves(tree))


def _masked_nll(logits, labels, mask):
    nll = token_nll(logits, labels) * mask
    return nll.sum(), mask.sum()


class DHPExecutor:
    def __init__(self, cfg: ModelConfig, devices=None, *,
                 model_axis: int = 1, pool: Optional[GroupPool] = None,
                 packed: Optional[bool] = None):
        """`pool` shares an externally owned GroupPool (e.g. the
        ClusterSpec's) so meshes/executables are reused across engines;
        by default the executor owns a fresh one over `devices`.

        `packed` selects the packed varlen execution path (default: on
        for families in PACKABLE_FAMILIES, off otherwise)."""
        if pool is not None:
            self.pool = pool
            self.devices = list(pool.devices.reshape(-1))
        else:
            self.devices = (devices if devices is not None
                            else jax.devices())
            self.pool = GroupPool(self.devices, model_axis)
        self.cfg_cp = cfg.with_(cp_axis="cp", scan_layers=True)
        self.cfg = cfg
        if packed is None:
            packed = cfg.family in PACKABLE_FAMILIES
        if packed and cfg.family not in PACKABLE_FAMILIES:
            raise ValueError(
                f"packed execution unsupported for family {cfg.family!r}"
                f" (needs segment-maskable attention + token-only batch)")
        self.packed = packed
        #: padding/compile telemetry of the most recent run_plan()
        #: (+ "modality_loss" sub-dict for span-bearing runs)
        self.last_run_stats: Dict[str, Any] = {}
        #: executable-pool keys dispatched by the most recent run_plan(),
        #: in dispatch order — the replay bit-identity witness (a plan
        #: saved with --save-plans must reproduce these exactly).
        self.last_exe_keys: List[Tuple] = []

    # ------------------------------------------------------------------
    def _build_grad_fn(self, mesh, with_spans: bool):
        """(loss, grads[, modality nll table]) step over a sub-mesh;
        batch seq-axis sharded.

        `with_spans` adds the modality_ids table (the mixed-mask
        bidirectional-block table), the `loss_mask` (labels inside
        bidirectional spans carry no NLL — they attend their own
        future) and the `modality_classes` label table to the sharded
        batch — only span-bearing groups compile/run the span-masked
        attention + masked-loss path; pure-causal groups keep the
        pre-span executable (and its exact numerics). Span-bearing
        steps return a [n_classes, 2] (nll_sum, label_count) aux table
        per MODALITY_CLASSES entry, reduced over the cp axis. A model
        with dropless expert layers returns its tables by name instead:
        "routes", the pairs routed to each held expert of each expert
        layer (int32 [layers, experts], summed over the cp axis), and
        "modality" in span-bearing steps."""
        cfg = self.cfg_cp
        routed = cfg.moe is not None and cfg.moe.dropless

        def build():
            pspec = P()     # params replicated on the sub-mesh (demo TP=1)
            keys = ("tokens", "labels", "mask", "positions")
            if with_spans:
                keys = keys + ("modality_ids", "loss_mask",
                               "modality_classes")
            if self.packed:
                keys = keys + ("segment_ids",)
            bspec = {k: P(None, "cp") for k in keys}

            def shard_loss(params, batch):
                if routed:
                    logits, _, routes = forward(params, cfg, batch,
                                                with_routes=True)
                    tables = {"routes": jax.lax.stop_gradient(
                        jax.lax.psum(routes, "cp"))}
                    loss, aux = span_loss(logits, batch) if with_spans \
                        else (causal_loss(logits, batch), None)
                    if aux is not None:
                        tables["modality"] = aux
                    return loss, tables
                logits, _ = forward(params, cfg, batch)
                if not with_spans:
                    return causal_loss(logits, batch)
                return span_loss(logits, batch)

            def causal_loss(logits, batch):
                s, c = _masked_nll(logits, batch["labels"], batch["mask"])
                s = jax.lax.psum(s, "cp")
                c = jax.lax.psum(c, "cp")
                return s / jnp.maximum(c, 1.0)

            def span_loss(logits, batch):
                nll = token_nll(logits, batch["labels"])
                lm = batch["loss_mask"]
                s = jax.lax.psum((nll * lm).sum(), "cp")
                c = jax.lax.psum(lm.sum(), "cp")
                # per-modality NLL over ALL valid labels (base mask):
                # classes excluded from the training loss still report
                cls = batch["modality_classes"]
                rows = []
                for k in range(len(MODALITY_CLASSES)):
                    mk = batch["mask"] * (cls == k)
                    rows.append(jnp.stack([(nll * mk).sum(), mk.sum()]))
                aux = jax.lax.psum(jnp.stack(rows), "cp")
                # telemetry only — a symbolic-Zero tangent for aux
                # would not transpose through shard_map
                return s / jnp.maximum(c, 1.0), jax.lax.stop_gradient(aux)

            has_aux = with_spans or routed

            def loss_of(params, batch):
                # params enter shard_map replicated (demo TP=1)
                out_specs = (P(), P()) if has_aux else P()
                # the grouped-matmul kernel states no varying axes for
                # its output: a routed model's step is not vma-checked
                kw = {"check_vma": False} if routed else {}
                return jax.shard_map(
                    shard_loss, mesh=mesh,
                    in_specs=(pspec, bspec), out_specs=out_specs, **kw,
                )(params, batch)

            def fwd_bwd(params, batch):
                if has_aux:
                    (loss, aux), grads = jax.value_and_grad(
                        loss_of, has_aux=True)(params, batch)
                    return loss, grads, aux
                loss, grads = jax.value_and_grad(loss_of)(params, batch)
                return loss, grads

            return jax.jit(fwd_bwd)

        return build

    def _group_grad_fn(self, start: int, degree: int, n_seqs: int,
                       bucket: int, with_spans: bool
                       ) -> Tuple[Any, bool, Tuple]:
        """Per-sequence-padded step for one CP group shape (legacy path:
        the executable key still depends on n_seqs)."""
        mesh = self.pool.mesh_for(start, degree)
        key = ("grad", start, degree, n_seqs, bucket) \
            + (("mm",) if with_spans else ())
        exe, miss = self.pool.executable_for(
            key, self._build_grad_fn(mesh, with_spans))
        return exe, miss, key

    def _packed_grad_fn(self, start: int, degree: int, bucket: int,
                        with_spans: bool) -> Tuple[Any, bool, Tuple]:
        """Packed varlen step: ONE [1, bucket] buffer regardless of how
        many sequences the group holds — n_seqs is gone from the key.
        Span-bearing groups get a distinct "mm" executable (their batch
        carries the modality table); causal groups keep the exact
        pre-span key tuple."""
        mesh = self.pool.mesh_for(start, degree)
        key = ("pgrad", start, degree, bucket) \
            + (("mm",) if with_spans else ())
        exe, miss = self.pool.executable_for(
            key, self._build_grad_fn(mesh, with_spans))
        return exe, miss, key

    # ------------------------------------------------------------------
    def _group_batch(self, seqs, degree: int, spans=None):
        """(np_batch, real_tokens, padded_tokens, bucket) for one group.

        `spans` (optional, parallel to `seqs`) carries each sequence's
        ModalitySpan layout; both paths emit the same per-sequence
        modality table, so packed and per-sequence execution apply the
        identical mixed mask."""
        if self.packed:
            total = sum(len(s) for s in seqs)
            bucket = self.pool.bucket(total)
            bucket += (-bucket) % degree       # shardable over cp
            np_batch, cu = flatten_group(seqs, bucket, spans=spans)
            return np_batch, int(cu[-1]), bucket, bucket
        bucket = self.pool.bucket(max(len(s) for s in seqs))
        bucket += (-bucket) % degree           # shardable over cp
        np_batch = padded_batch(seqs, bucket, spans=spans)
        real = sum(min(len(s), bucket) for s in seqs)
        return np_batch, real, len(seqs) * bucket, bucket

    # ------------------------------------------------------------------
    def home(self, params) -> NamedSharding:
        """Where `run_plan` sums the gradients: replicated over the
        devices that hold `params`, on a mesh named like the group
        meshes (for parameters on one device, the degree-1 group mesh
        there). Callers that commit their state here keep one placement,
        and so one compiled program, from the first step on."""
        devs = sorted(jax.tree.leaves(params)[0].devices(),
                      key=lambda d: d.id)
        return NamedSharding(Mesh(np.array(devs).reshape(-1, 1),
                                  self.pool.axis_names), P())

    # ------------------------------------------------------------------
    def run_plan(self, params, plan: ExecutionPlan, data: RaggedBatch,
                 *, timings: Optional[List[Dict[str, Any]]] = None
                 ) -> Tuple[jax.Array, Any]:
        """Execute every micro-batch of the plan; returns
        (mean loss, token-weighted mean gradient) for the global batch.

        When `timings` (a caller-owned list) is passed, each group is
        executed SYNCHRONOUSLY and a record {seq_ids, degree, tokens,
        bucket, seconds, compiled, real_tokens, padded_tokens,
        padding_efficiency} is appended per group — the measured-cost
        feed for `repro.api.OracleStrategy` (padding fields let it see
        TRUE per-token costs, not padded-shape artefacts). This trades
        away the concurrent dispatch of disjoint groups, so only enable
        it when measuring.

        `self.last_run_stats` always aggregates {real_tokens,
        padded_tokens, padding_efficiency, exe_misses, groups,
        grad_d2d_bytes} for the run — the benchmark/CI telemetry feed;
        grad_d2d_bytes counts the group outputs copied device to device
        onto the accumulator's devices. Span-bearing runs add
        "modality_loss": {class name: mean NLL} over every class that
        had at least one valid label (classes masked OUT of the
        training loss, e.g. bidirectional vision spans, still report)."""
        import time as _time
        tr = get_tracer()
        t_run = _time.perf_counter()
        total_tokens = 0.0
        acc = None           # (loss, grads, aux) sums, on the device
        # the accumulator lives where the parameters the update reads do
        home = self.home(params)
        agg: Dict[str, Any] = {"real_tokens": 0, "padded_tokens": 0,
                               "exe_misses": 0, "groups": 0,
                               "grad_d2d_bytes": 0}
        # Rank slots come from the plan IR itself (including the
        # defensive wrap for oversubscribed micro-batches) so executor,
        # GroupDelta diffing and replay equality all agree on which rank
        # slice a group runs on.
        slots = iter(plan.group_slots(self.pool.n_replicas))
        n_groups = sum(len(mb.groups) for mb in plan.micro_batches)
        summed = 0
        self.last_exe_keys = []
        spans_by_id = (data.spans_by_id()
                       if hasattr(data, "spans_by_id") else {})
        for mb in plan.micro_batches:
            handles = []
            for g in mb.groups:
                mi, gi, start, _ = next(slots)
                seqs = [data.by_id(i) for i in g.seq_ids]
                spans = ([spans_by_id.get(i) for i in g.seq_ids]
                         if spans_by_id else None)
                np_batch, real, padded, bucket = self._group_batch(
                    seqs, g.degree, spans=spans)
                with_spans = "modality_ids" in np_batch
                if self.packed:
                    step, compiled, key = self._packed_grad_fn(
                        start, g.degree, bucket, with_spans)
                else:
                    step, compiled, key = self._group_grad_fn(
                        start, g.degree, len(seqs), bucket, with_spans)
                self.last_exe_keys.append(key)
                batch = {k: jnp.asarray(v) for k, v in np_batch.items()}
                # the parameters go to the group's sub-mesh: device to
                # device off the accumulator's devices, as they are on it
                group_params = jax.device_put(params, NamedSharding(
                    self.pool.mesh_for(start, g.degree), P()))
                # weight groups by LOSS tokens when a loss mask exists —
                # bidirectional-span labels carry no NLL, so counting
                # them would dilute the span-bearing groups' gradients
                n_tok = float(np_batch.get(
                    "loss_mask", np_batch["mask"]).sum())
                agg["real_tokens"] += real
                agg["padded_tokens"] += padded
                agg["exe_misses"] += int(compiled)
                agg["groups"] += 1
                if timings is None:
                    t0 = _time.perf_counter()
                    handles.append((step(group_params, batch), n_tok))  # async
                    if tr.enabled:
                        # host-side dispatch cost only: the device work
                        # runs asynchronously and is not observable
                        # per group on this path
                        tr.complete("dispatch", t0,
                                    _time.perf_counter() - t0, "exec",
                                    args={"mb": mi, "group": gi,
                                          "degree": g.degree,
                                          "start_rank": start})
                else:
                    t0 = _time.perf_counter()
                    out = jax.block_until_ready(step(group_params, batch))
                    dt = _time.perf_counter() - t0
                    timings.append({
                        "seq_ids": list(g.seq_ids),
                        "degree": g.degree,
                        "tokens": g.tokens,
                        "bucket": bucket,
                        "seconds": dt,
                        "compiled": compiled,
                        "real_tokens": real,
                        "padded_tokens": padded,
                        "padding_efficiency": real / max(padded, 1),
                    })
                    if tr.enabled:
                        # measured group time becomes ONE span on the
                        # track of every rank the group occupies — the
                        # per-rank timeline the straggler analytics read
                        for rank in range(start, start + g.degree):
                            tr.rank_span(
                                "execute", rank, t0, dt,
                                args={"mb": mi, "group": gi,
                                      "degree": g.degree,
                                      "tokens": g.tokens,
                                      "compiled": compiled})
                    handles.append((out, n_tok))
            t_collect = _time.perf_counter()
            for out, n_tok in handles:
                # sum: dispatch the group's add into the accumulator;
                # nothing here waits for the device
                t0 = _time.perf_counter()
                if not all(x.sharding.is_equivalent_to(home, x.ndim)
                           for x in jax.tree.leaves(out)):
                    # the group ran off the accumulator's devices: its
                    # outputs cross device to device, never via the host
                    agg["grad_d2d_bytes"] += _tree_nbytes(out)
                    out = jax.device_put(out, home)
                total_tokens += n_tok
                summed += 1
                acc = accumulate_grads(
                    acc, out, n_tok,
                    max(total_tokens, 1.0) if summed == n_groups else None)
                if tr.enabled:
                    tr.complete("collect.sum", t0,
                                _time.perf_counter() - t0, "exec")
            if tr.enabled:
                tr.complete("collect", t_collect,
                            _time.perf_counter() - t_collect, "exec",
                            args={"groups": len(handles)})
        agg["padding_efficiency"] = (
            agg["real_tokens"] / max(agg["padded_tokens"], 1))
        loss, grads, aux = acc
        tables = {}
        if aux is not None:
            # the one host read of the run, after the last dispatch
            aux = jax.device_get(aux)
            tables = aux if isinstance(aux, dict) else {"modality": aux}
        if "modality" in tables:
            aux = np.asarray(tables["modality"], np.float64)
            agg["modality_loss"] = {
                name: float(aux[k, 0] / aux[k, 1])
                for k, name in enumerate(MODALITY_CLASSES)
                if aux[k, 1] > 0}
        if "routes" in tables:
            routes = np.asarray(tables["routes"], np.int64)
            agg["moe_routed_pairs"] = int(routes.sum())
            agg["moe_max_expert_pairs"] = int(routes.max())
            if tr.enabled:
                tr.counter("moe.routes", {
                    "routed_pairs": agg["moe_routed_pairs"],
                    "max_expert_pairs": agg["moe_max_expert_pairs"]})
        self.last_run_stats = agg
        if tr.enabled:
            tr.complete("run_plan", t_run,
                        _time.perf_counter() - t_run, "exec",
                        args={"groups": agg["groups"],
                              "exe_misses": agg["exe_misses"],
                              "measured": timings is not None})
        return loss, grads
