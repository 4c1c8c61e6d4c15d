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
dynamic regrouping changes WHERE sequences run, never the math.

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
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..data.pipeline import RaggedBatch, padded_batch
from ..models.model import forward
from ..obs.trace import get_tracer
from ..training.optimizer import AdamW
from .group_pool import GroupPool
from .packing import MODALITY_CLASSES, flatten_group
from .scheduler import ExecutionPlan

#: families whose attention layers support block-diagonal segment masks;
#: recurrent state (ssm/hybrid) crosses segment boundaries, and
#: vlm/audio batches carry extra modal inputs the flattener doesn't pack.
PACKABLE_FAMILIES = ("dense", "moe")


def _token_nll(logits, labels):
    """Per-position next-token NLL (no masking applied)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - gold


def _masked_nll(logits, labels, mask):
    nll = _token_nll(logits, labels) * mask
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
        per MODALITY_CLASSES entry, reduced over the cp axis."""
        cfg = self.cfg_cp

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
                logits, _ = forward(params, cfg, batch)
                if not with_spans:
                    s, c = _masked_nll(logits, batch["labels"],
                                       batch["mask"])
                    s = jax.lax.psum(s, "cp")
                    c = jax.lax.psum(c, "cp")
                    return s / jnp.maximum(c, 1.0)
                nll = _token_nll(logits, batch["labels"])
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

            def loss_of(params, batch):
                # params enter shard_map replicated (demo TP=1)
                out_specs = (P(), P()) if with_spans else P()
                return jax.shard_map(
                    shard_loss, mesh=mesh,
                    in_specs=(pspec, bspec), out_specs=out_specs,
                )(params, batch)

            def fwd_bwd(params, batch):
                if with_spans:
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
        padded_tokens, padding_efficiency, exe_misses, groups} for the
        run — the benchmark/CI telemetry feed. Span-bearing runs add
        "modality_loss": {class name: mean NLL} over every class that
        had at least one valid label (classes masked OUT of the
        training loss, e.g. bidirectional vision spans, still report)."""
        import time as _time
        tr = get_tracer()
        t_run = _time.perf_counter()
        total_tokens = 0.0
        g_acc = None
        loss_acc = 0.0
        aux_acc = None       # [n_classes, 2] (nll_sum, label_count)
        agg: Dict[str, Any] = {"real_tokens": 0, "padded_tokens": 0,
                               "exe_misses": 0, "groups": 0}
        # Rank slots come from the plan IR itself (including the
        # defensive wrap for oversubscribed micro-batches) so executor,
        # GroupDelta diffing and replay equality all agree on which rank
        # slice a group runs on.
        slots = iter(plan.group_slots(self.pool.n_replicas))
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
                    handles.append((step(params, batch), n_tok))  # async
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
                    out = jax.block_until_ready(step(params, batch))
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
                loss, grads = out[0], out[1]
                if len(out) > 2:           # span-bearing: modality aux
                    a = np.asarray(out[2], np.float64)
                    aux_acc = a if aux_acc is None else aux_acc + a
                w = n_tok
                total_tokens += w
                loss_acc += float(loss) * w
                g_np = jax.tree.map(
                    lambda a: np.asarray(a, np.float32) * w, grads)
                g_acc = g_np if g_acc is None else jax.tree.map(
                    np.add, g_acc, g_np)
            if tr.enabled:
                # draining the handles forces the device sync for this
                # micro-batch — the wave barrier
                tr.complete("collect", t_collect,
                            _time.perf_counter() - t_collect, "exec",
                            args={"groups": len(handles)})
        agg["padding_efficiency"] = (
            agg["real_tokens"] / max(agg["padded_tokens"], 1))
        if aux_acc is not None:
            agg["modality_loss"] = {
                name: float(aux_acc[k, 0] / aux_acc[k, 1])
                for k, name in enumerate(MODALITY_CLASSES)
                if aux_acc[k, 1] > 0}
        self.last_run_stats = agg
        denom = max(total_tokens, 1.0)
        grads = jax.tree.map(lambda a: jnp.asarray(a / denom), g_acc)
        if tr.enabled:
            tr.complete("run_plan", t_run,
                        _time.perf_counter() - t_run, "exec",
                        args={"groups": agg["groups"],
                              "exe_misses": agg["exe_misses"],
                              "measured": timings is not None})
        return jnp.asarray(loss_acc / denom), grads
