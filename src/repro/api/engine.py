"""Engine / Session — the single public entry point of the repro.

One facade owns the full lifecycle the paper's Fig. 3 describes:

    ClusterSpec  ──►  Engine(model, cluster, strategy="dhp")
                         │ plan(batch)    -> ExecutionPlan
                         │ execute(plan)  -> StepMetrics
                         │ train(loader)  -> [StepMetrics]  (async built in)
                         │ serve(...)     -> decoded tokens
                         ▼
                      Strategy registry (static / dhp / bruteforce / oracle)

`train()` is the one driver every launcher/example/benchmark shares: a
producer-consumer loop that prepares the NEXT batch's plan on a host
thread while devices execute the current one (paper §5 Implementation
(2)), parameterized only by the strategy name.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Dict, Iterable, Iterator, List, Optional, Union

from ..configs import get_config
from ..configs.base import ModelConfig
from ..core.cost_model import (CostModel, SeqInfo, analytic_coeffs,
                               model_costs)
from ..core.executor import DHPExecutor
from ..core.scheduler import ExecutionPlan, diff_plans
from ..data.pipeline import HeterogeneousLoader, RaggedBatch
from ..obs import (MetricsRegistry, RunRecorder, RunReport, Tracer,
                   build_report, step_model_error, tracing)
from .cluster import ClusterSpec
from .strategies import Strategy, get_strategy

Batch = Union[RaggedBatch, List[SeqInfo]]

#: XLA module name of the optimizer update (`apply_update` in
#: `Engine.update_fn`): the chip benchmark's device-trace readers find
#: the program by this name
UPDATE_MODULE = "jit_apply_update"


@dataclasses.dataclass
class StepMetrics:
    """What one executed plan produced — the uniform result row every
    driver prints and every benchmark aggregates."""

    step: int
    loss: float
    tokens: int
    step_time_s: float
    strategy: str
    schedule_ms: float
    solver_ms: float
    stage_ms: Dict[str, float]
    degree_histogram: Dict[int, int]
    #: real/padded token ratio of the executed step (1.0 = no padding)
    padding_efficiency: float = 1.0
    #: executables compiled during this step (0 once the pool is warm)
    exe_misses: int = 0
    #: True when the plan came from the strategy's PlanCache (the DP
    #: solver was skipped for a recurring batch shape)
    plan_cache_hit: bool = False
    #: group slots created/resized vs the previous plan (GroupDelta)
    groups_reconfigured: int = 0
    #: planning latency hidden behind device execution by the lookahead
    #: pipeline (schedule_ms minus the time collect() actually blocked)
    plan_overlap_ms: float = 0.0
    #: tokens per modality in the executed batch ({"text": .., "vision":
    #: ..}); sequences without span structure count as "text"
    modality_tokens: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    #: Stage-2 allocator time for this plan (cost table + DP), in us —
    #: the millisecond-class-planning budget check_regression gates
    allocate_us: float = 0.0
    #: which planning path produced the plan: "full" | "incremental"
    #: (warm-started DP suffix) | "cache" (PlanCache hit)
    replan_mode: str = "full"
    #: mean next-token NLL per label-token modality class for
    #: span-bearing batches ({"text": .., "vision": ..}). Classes whose
    #: labels are excluded from the TRAINING loss (bidirectional spans)
    #: still report their NLL here for monitoring.
    modality_loss: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    #: cost-model MAPE of this step's scaled predicted vs measured
    #: group times (obs.report.step_model_error); 0.0 on unmeasured
    #: steps and steps where every group paid XLA compilation
    model_error_pct: float = 0.0
    #: the strategy's PlanCache.stats snapshot after this step (hits,
    #: misses, size, nearest_* reference counters); {} when caching off
    plan_cache: Dict[str, int] = dataclasses.field(default_factory=dict)

    def summary(self) -> str:
        cached = " cached" if self.plan_cache_hit else ""
        return (f"step {self.step:3d} loss={self.loss:.4f} "
                f"degrees={self.degree_histogram} "
                f"sched={self.schedule_ms:.1f}ms{cached} "
                f"reconf={self.groups_reconfigured} "
                f"({self.step_time_s:.2f}s)")

    # -- serialization: THE StepMetrics wire format ---------------------
    def to_json(self) -> dict:
        """JSON-serializable dict; `from_json` round-trips it exactly.
        Every consumer (Engine history dumps, benchmarks, the obs run
        report) uses this instead of ad-hoc field plucking."""
        d = dataclasses.asdict(self)
        # JSON object keys are strings; stringify the int degree keys
        d["degree_histogram"] = {str(k): v for k, v
                                 in self.degree_histogram.items()}
        return d

    @classmethod
    def from_json(cls, obj: dict) -> "StepMetrics":
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in obj.items() if k in names}
        kw["degree_histogram"] = {
            int(k): int(v)
            for k, v in (kw.get("degree_histogram") or {}).items()}
        return cls(**kw)


def metrics_to_json(history: List["StepMetrics"]) -> dict:
    """A training history as one JSON document (the --metrics file)."""
    return {"version": 1, "steps": [m.to_json() for m in history]}


def metrics_from_json(obj: dict) -> List["StepMetrics"]:
    steps = obj["steps"] if isinstance(obj, dict) else obj
    return [StepMetrics.from_json(s) for s in steps]


def demo_cost_model(cfg: ModelConfig) -> CostModel:
    """The CPU-demo calibration every driver used to hand-roll: roofline
    coefficients for the model shape, with memory accounting in plain
    tokens (m_token=1, m_ms=0) so `mem_budget` reads as a per-rank token
    budget."""
    coeffs = dataclasses.replace(
        analytic_coeffs(
            hidden=cfg.d_model, n_layers=cfg.n_layers,
            n_heads=max(cfg.n_heads, 1), kv_heads=max(cfg.kv_heads, 1),
            ffn=max(cfg.d_ff, 1), vocab=cfg.vocab, **model_costs(cfg)),
        m_ms=0.0, m_token=1.0)
    return CostModel(coeffs)


class Engine:
    """A training/serving session on one cluster with one swappable
    parallelism strategy.

    >>> eng = Engine("internvl3-2b", strategy="dhp", reduced=True)
    >>> metrics = eng.train(steps=5, dataset="openvid", global_batch=8)

    `model` is an arch id from the registry or a ModelConfig. VLM
    configs are run in token-stream mode (vision tokens pre-counted in
    the SeqInfo lengths, LM decoder executed) — the convention the DHP
    loader/executor pair uses throughout.
    """

    def __init__(self, model: Union[str, ModelConfig],
                 cluster: Optional[ClusterSpec] = None, *,
                 strategy: Union[str, Strategy] = "dhp",
                 optimizer: Optional[Any] = None,
                 cost_model: Optional[CostModel] = None,
                 reduced: bool = False,
                 packed: Optional[bool] = None,
                 seed: int = 0):
        """`packed` forwards to DHPExecutor: the packed varlen execution
        path (default: on for attention families)."""
        cfg = get_config(model) if isinstance(model, str) else model
        if reduced:
            cfg = cfg.reduced()
        if cfg.family == "vlm":
            cfg = cfg.with_(family="dense", vlm=None)
        self.cfg = cfg
        self._packed = packed
        self.cluster = cluster or ClusterSpec.auto()
        self.cost_model = cost_model or demo_cost_model(cfg)
        self.strategy = (get_strategy(strategy)
                         if isinstance(strategy, str) else strategy)
        self.strategy.bind(self.cost_model, self.cluster.n_replicas,
                           self.cluster.mem_budget)
        self.seed = seed
        self._optimizer = optimizer
        self._state = None
        self._executor: Optional[DHPExecutor] = None
        self._apply_update = None
        self._step = 0
        self._prev_plan: Optional[ExecutionPlan] = None
        #: session-lifetime counters/gauges/histograms (obs.metrics);
        #: updated by every execute(), snapshot() at any point
        self.metrics = MetricsRegistry()
        #: per-group (predicted, measured, rank-slot) records feeding
        #: the run report; installed by train(report=)
        self._recorder: Optional[RunRecorder] = None
        #: the RunReport of the last reported train() call
        self.last_report: Optional[RunReport] = None
        #: the loader train() last built/used — checkpointed so resume
        #: replays the exact remaining batch stream
        self.loader = None
        self._loader_state: Optional[dict] = None

    # -- lazy heavyweight pieces ----------------------------------------
    @property
    def executor(self) -> DHPExecutor:
        if self._executor is None:
            self._executor = DHPExecutor(self.cfg,
                                         pool=self.cluster.pool(),
                                         packed=self._packed)
        return self._executor

    @property
    def optimizer(self):
        if self._optimizer is None:
            from ..training.optimizer import AdamW
            self._optimizer = AdamW(lr=3e-4)
        return self._optimizer

    @property
    def state(self):
        if self._state is None:
            self._state = self.init_state(self.seed)
        return self._state

    @state.setter
    def state(self, value):
        self._state = value

    def init_state(self, seed: int = 0):
        import jax
        from ..models.model import init_params
        from ..training.train_step import TrainState
        params = init_params(jax.random.PRNGKey(seed), self.cfg)
        return TrainState(params=params,
                          opt=self.optimizer.init(params))

    # -- plan -----------------------------------------------------------
    def plan(self, batch: Batch) -> ExecutionPlan:
        """Plan one global batch with the session's strategy."""
        infos = batch.infos if isinstance(batch, RaggedBatch) else batch
        return self.strategy.plan(infos)

    # -- execute --------------------------------------------------------
    def update_fn(self):
        """The jitted optimizer update `(state, grads) -> state`, built
        once per engine; it donates the state it is given."""
        if self._apply_update is None:
            import jax

            from ..training.train_step import TrainState
            opt = self.optimizer

            # donating the state lets the new state reuse its buffers:
            # without it the old and new params + fp32 moments are live
            # at once, which does not fit one 16 GB chip at internvl3-2b
            # widths
            @partial(jax.jit, donate_argnums=0)
            def apply_update(state, grads):
                p, o = opt.update(grads, state.opt, state.params)
                return TrainState(p, o)

            self._apply_update = apply_update
        return self._apply_update

    def execute(self, plan: ExecutionPlan, data: RaggedBatch, *,
                update: bool = True,
                measure: Optional[bool] = None) -> StepMetrics:
        """Run a plan on the cluster; optionally apply the optimizer
        update. `measure` forces per-group timing capture (defaults to
        whatever the strategy asks for — OracleStrategy wants it)."""
        import jax

        if measure is None:
            # an installed recorder needs per-group timings too (the
            # run report's imbalance/straggler/MAPE inputs)
            measure = (self.strategy.wants_measurement
                       or self._recorder is not None)
        # Group-reconfiguration delta vs the previously executed plan:
        # the pool consumes it (reused slots cost nothing, new/resized
        # slots are created) instead of re-deriving every group.
        if plan.delta is None:
            plan.delta = diff_plans(self._prev_plan, plan,
                                    self.cluster.n_replicas)
        self.executor.pool.reconfigure(plan.delta)
        self._prev_plan = plan
        timings: Optional[List[dict]] = [] if measure else None
        t0 = time.perf_counter()
        # the state lives where the executor sums the gradients,
        # committed there from the first step on (no copy), so the grad
        # step and the update see one placement and compile once
        self.state = jax.device_put(
            self.state, self.executor.home(self.state.params))
        loss, grads = self.executor.run_plan(self.state.params, plan,
                                             data, timings=timings)
        if update:
            self.state = self.update_fn()(self.state, grads)
        # the step ends when the device has the new state
        jax.block_until_ready(self.state if update else grads)
        step_time = time.perf_counter() - t0
        model_error = 0.0
        if timings:
            self.strategy.observe(plan, timings)
            model_error = step_model_error(plan, timings)
            if self._recorder is not None:
                self._recorder.record_step(self._step, plan, timings)
        mod_tokens: Dict[str, int] = {}
        for s in data.infos:
            spans = getattr(s, "spans", None)
            if spans:
                for sp in spans:
                    mod_tokens[sp.modality] = (
                        mod_tokens.get(sp.modality, 0) + sp.length)
            else:
                mod_tokens["text"] = mod_tokens.get("text", 0) + s.length
        metrics = StepMetrics(
            step=self._step,
            loss=float(loss),
            tokens=sum(g.tokens for mb in plan.micro_batches
                       for g in mb.groups),
            step_time_s=step_time,
            strategy=plan.strategy_name or self.strategy.name,
            schedule_ms=plan.schedule_ms,
            solver_ms=plan.solver_ms,
            stage_ms=dict(plan.stage_ms),
            degree_histogram=plan.degree_histogram,
            padding_efficiency=self.executor.last_run_stats.get(
                "padding_efficiency", 1.0),
            exe_misses=self.executor.last_run_stats.get("exe_misses", 0),
            plan_cache_hit=plan.from_cache,
            groups_reconfigured=plan.delta.n_reconfigured,
            modality_tokens=mod_tokens,
            allocate_us=plan.stage_ms.get("allocate", 0.0) * 1e3,
            replan_mode=plan.replan_mode,
            modality_loss=dict(self.executor.last_run_stats.get(
                "modality_loss", {})),
            model_error_pct=model_error,
            plan_cache=(dict(self.strategy.plan_cache.stats)
                        if self.strategy.plan_cache is not None else {}),
        )
        self._step += 1
        self._update_metrics(metrics, measured=bool(timings))
        return metrics

    def _update_metrics(self, m: StepMetrics, *, measured: bool) -> None:
        """Fold one step's signals into the session metrics registry."""
        reg = self.metrics
        reg.counter("train/steps").inc()
        reg.counter("train/tokens").inc(m.tokens)
        reg.counter("pool/exe_misses").inc(m.exe_misses)
        reg.counter("pool/groups_reconfigured").inc(
            m.groups_reconfigured)
        reg.counter("plan/steps_from_cache").inc(int(m.plan_cache_hit))
        reg.histogram("plan/schedule_ms").observe(m.schedule_ms)
        reg.histogram("plan/allocate_us").observe(m.allocate_us)
        reg.histogram("exec/step_time_s").observe(m.step_time_s)
        reg.histogram("exec/padding_efficiency").observe(
            m.padding_efficiency)
        if measured:
            reg.histogram("cost_model/error_pct").observe(
                m.model_error_pct)
        # cumulative cache/pool state lands as gauges under distinct
        # prefixes so they cannot collide with the per-step counters
        reg.update_from(m.plan_cache, "plan/cache_")
        reg.update_from(vars(self.executor.pool.stats), "pool/total_")

    # -- train: THE loop ------------------------------------------------
    def train(self, loader: Optional[Iterable[RaggedBatch]] = None, *,
              steps: int = 10, dataset: str = "openvid",
              global_batch: int = 8, max_tokens: int = 512,
              tokens_per_frame: int = 16,
              lookahead: Union[bool, int] = True,
              plan_log: Optional[List[ExecutionPlan]] = None,
              log=None,
              trace: Union[None, bool, str, Tracer] = None,
              report: Union[None, bool, str] = None
              ) -> List[StepMetrics]:
        """The single training driver: heterogeneous batches -> strategy
        plan -> executor. Every strategy (static baselines included)
        runs through this one loop.

        `lookahead=True` (default) runs the planner pipeline: a
        background host thread plans batch t+1 while devices execute
        batch t, and `StepMetrics.plan_overlap_ms` reports how much
        planning latency that hid. An int widens the window: batches
        t+1..t+k are enqueued to the planner thread, which solves them
        back-to-back sharing the scheduler's warm allocator state (the
        batched-lookahead contract — see docs/api.md "Planner
        performance"). `lookahead=False` is the synchronous baseline —
        plan, then execute, back to back.

        `plan_log`: pass a list to receive every executed ExecutionPlan
        (the `--save-plans` trace).

        `trace`: a path (Chrome trace-event JSON is saved there), True,
        or a Tracer instance — records the run's timeline: scheduler
        stages and the lookahead planner thread on host tracks, the
        executor's dispatch and collect spans (load the file at
        https://ui.perfetto.dev); groups keep their concurrent
        dispatch. `report`: a path or True — builds the post-run
        analytics RunReport (per-wave imbalance, per-rank straggler
        scores, cost-model MAPE), kept on `self.last_report` and saved
        as JSON when a path is given. `report` switches execution to
        measuring mode (per-group synchronous timing; with a tracer,
        measured group execution on one track per simulated rank), so
        the concurrent dispatch of disjoint groups is traded for
        observability — see docs/api.md "Observability"."""
        tracer: Optional[Tracer] = None
        trace_path: Optional[str] = None
        if trace is not None and trace is not False:
            if isinstance(trace, str):
                trace_path, tracer = trace, Tracer()
            elif trace is True:
                tracer = Tracer()
            else:
                tracer = trace
        observing = bool(report)
        if observing:
            self._recorder = RunRecorder(self.cluster.n_replicas)
        history: List[StepMetrics] = []
        try:
            if tracer is not None:
                with tracing(tracer):
                    self._train_loop(loader, steps, dataset,
                                     global_batch, max_tokens,
                                     tokens_per_frame, lookahead,
                                     plan_log, log, history)
            else:
                self._train_loop(loader, steps, dataset, global_batch,
                                 max_tokens, tokens_per_frame,
                                 lookahead, plan_log, log, history)
        finally:
            if observing:
                self.last_report = build_report(
                    self._recorder, history,
                    metrics=self.metrics.snapshot())
                self._recorder = None
                if isinstance(report, str):
                    self.last_report.save(report)
            if trace_path is not None:
                tracer.save(trace_path)
        return history

    def _train_loop(self, loader, steps, dataset, global_batch,
                    max_tokens, tokens_per_frame, lookahead, plan_log,
                    log, history: List[StepMetrics]) -> None:
        if loader is None:
            loader = HeterogeneousLoader(
                dataset, global_batch, self.cfg.vocab, seed=self.seed,
                max_tokens=max_tokens, tokens_per_frame=tokens_per_frame)
        if self._loader_state is not None and hasattr(loader, "set_state"):
            # a checkpoint restore left a stream position to resume from
            loader.set_state(self._loader_state)
            self._loader_state = None
        self.loader = loader
        it: Iterator[RaggedBatch] = iter(loader)

        # lookahead depth: 0 = synchronous, k >= 1 = plans for batches
        # t+1..t+k kept in flight on the planner thread.
        depth = (1 if lookahead is True
                 else 0 if lookahead is False else max(0, int(lookahead)))
        try:
            data = next(it)
        except StopIteration:
            return
        n_fetched = 1
        if depth:
            self.strategy.prepare(data.infos)
        from collections import deque
        queue: "deque[RaggedBatch]" = deque()   # fetched, plan in flight
        for i in range(steps):
            if depth:
                plan = self.strategy.collect()
                overlap = max(
                    0.0, plan.schedule_ms - self.strategy.last_wait_ms)
            else:
                plan = self.strategy.plan(data.infos)
                overlap = 0.0
            # Top up the prefetch window — but only with batches that
            # WILL execute (n_fetched < steps): consuming a batch (or
            # popping a replay plan) that never runs would desync
            # resumable loaders and ReplayStrategy's cursor.
            while n_fetched < steps and len(queue) < max(depth, 1):
                try:
                    nxt = next(it)
                except StopIteration:
                    break
                queue.append(nxt)
                n_fetched += 1
                if depth:
                    self.strategy.prepare(nxt.infos)  # overlap planning
            metrics = self.execute(plan, data)
            metrics.plan_overlap_ms = overlap
            if plan_log is not None:
                plan_log.append(plan)
            history.append(metrics)
            if log is not None:
                log(metrics.summary())
            if not queue:
                break
            data = queue.popleft()

    # -- serve ----------------------------------------------------------
    def serve(self, prompts=None, *, batch: int = 8,
              prompt_len: int = 96, gen_tokens: int = 32,
              cache_len: Optional[int] = None):
        """Batched prefill + greedy decode via serving/serve_step.

        `prompts`: [B, S] int32 token ids (random ids drawn when None).
        Attention families (dense/moe/vlm) prefill a KV cache;
        ssm/recurrent/hybrid families start from a fresh state cache and
        audio additionally prefills the encoder cross-KV from synthetic
        frames — the same per-family routing the pre-API quickstart did.
        Returns (decoded [B, gen_tokens] tokens, dict of timings)."""
        import jax
        import jax.numpy as jnp

        from ..models.model import (init_cache, prefill,
                                    prefill_cross_kv)
        from ..serving.serve_step import greedy_generate, make_serve_step

        if prompts is None:
            prompts = jax.random.randint(
                jax.random.PRNGKey(self.seed + 1), (batch, prompt_len),
                0, self.cfg.vocab)
        prompts = jnp.asarray(prompts)
        batch, prompt_len = prompts.shape
        cache_len = cache_len or prompt_len + gen_tokens

        t0 = time.perf_counter()
        if self.cfg.family in ("dense", "moe", "vlm"):
            logits, cache = prefill(self.state.params, self.cfg,
                                    {"tokens": prompts},
                                    cache_len=cache_len)
            first = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
        else:
            cache = init_cache(self.cfg, batch, cache_len)
            if self.cfg.family == "audio":
                frames = jax.random.normal(
                    jax.random.PRNGKey(self.seed + 2),
                    (batch, self.cfg.encdec.n_audio_frames,
                     self.cfg.d_model))
                cache = prefill_cross_kv(self.state.params, self.cfg,
                                         frames, cache)
            first = prompts[:, -1].astype(jnp.int32)
        t_prefill = time.perf_counter() - t0

        # The decode step lives in the cluster's shared executable pool
        # (same cache the training groups use), keyed on the shapes that
        # force recompilation — repeat serve calls skip the jit.
        step, step_miss = self.cluster.pool().executable_for(
            ("serve", self.cfg.arch_id, self.cfg.family, batch,
             cache_len),
            lambda: jax.jit(make_serve_step(self.cfg)))
        t0 = time.perf_counter()
        out, cache = greedy_generate(self.state.params, self.cfg, cache,
                                     first, gen_tokens, step=step)
        t_decode = time.perf_counter() - t0
        report = {
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "ms_per_token": t_decode / max(gen_tokens, 1) * 1e3,
            "batch": batch,
            "prompt_len": prompt_len,
            "exe_miss": step_miss,
        }
        return out, report

    # -- serving ---------------------------------------------------------
    def serving(self, *, slots: int = 4, prefill_chunk: int = 128,
                cache_len: Optional[int] = None, block_size: int = 16,
                n_blocks: Optional[int] = None, strategy: str = "dhp"):
        """The continuous-batching runtime over this engine's model and
        cluster (serving/runtime.py): paged KV slots, DHP-planned
        chunked prefill, iteration-level batching. `serve()` below stays
        the one-shot fixed-batch path."""
        from ..serving.runtime import ServingEngine
        return ServingEngine(
            self.cfg, self.state.params, self.cluster, self.cost_model,
            slots=slots, cache_len=cache_len, block_size=block_size,
            n_blocks=n_blocks, prefill_chunk=prefill_chunk,
            strategy=strategy, seed=self.seed)

    # -- checkpointing ---------------------------------------------------
    def save_checkpoint(self, path: str) -> None:
        """Full train-state snapshot: params + optimizer moments + step
        counter + (when train() ran with a resumable loader) the data
        stream position — everything a bit-identical resume needs."""
        from ..training.checkpoint import save
        meta: Dict[str, Any] = {"format": 2, "step": self._step}
        if self.loader is not None and hasattr(self.loader, "state"):
            meta["loader"] = self.loader.state()
        save(path, {"params": self.state.params, "opt": self.state.opt},
             meta=meta)

    def load_checkpoint(self, path: str) -> None:
        from ..training.checkpoint import load_meta, restore
        meta = load_meta(path)
        if meta is None:
            # pre-format-2 checkpoint: params only, no meta blob
            self.state = self.state._replace(
                params=restore(path, self.state.params))
            return
        tree = restore(path, {"params": self.state.params,
                              "opt": self.state.opt})
        self.state = self.state._replace(params=tree["params"],
                                         opt=tree["opt"])
        self._step = int(meta.get("step", self._step))
        self._loader_state = meta.get("loader")

    def close(self) -> None:
        self.strategy.close()


#: `Session` is the facade name from the API docs; `Engine` the original.
Session = Engine
