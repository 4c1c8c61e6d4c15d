"""The unified `repro.api` engine: strategy registry round-trip,
Engine.plan/execute smoke on the 8-host-device CPU demo mesh, the
OracleStrategy measured-cost loop, and the backward-compat import
surface."""
import dataclasses

import numpy as np
import pytest

from repro.api import (ClusterSpec, BruteForceStrategy, DHPStrategy,
                       Engine, MeasuredCostModel, OracleStrategy,
                       Session, StaticStrategy, Strategy,
                       available_strategies, demo_cost_model,
                       get_strategy, register_strategy)
from repro.core import CostModel, SeqInfo, analytic_coeffs

CM = CostModel(dataclasses.replace(
    analytic_coeffs(hidden=1024, n_layers=8, n_heads=8, kv_heads=4,
                    ffn=4096, vocab=32000),
    m_ms=0.0, m_token=1.0))


def _seqs(lengths):
    return [SeqInfo(length=n, seq_id=i) for i, n in enumerate(lengths)]


# ------------------------------------------------------------ registry
def test_registry_round_trip():
    expected = {"static": StaticStrategy, "megatron": StaticStrategy,
                "deepspeed": StaticStrategy, "dhp": DHPStrategy,
                "dhp-faithful": DHPStrategy,
                "bruteforce": BruteForceStrategy,
                "oracle": OracleStrategy}
    assert set(expected) <= set(available_strategies())
    for name, cls in expected.items():
        strat = get_strategy(name)
        assert isinstance(strat, cls), name
        assert strat.name == name
        assert not strat.is_bound


def test_registry_unknown_name():
    with pytest.raises(KeyError, match="unknown strategy"):
        get_strategy("nope")


def test_registry_defaults_and_overrides():
    assert get_strategy("deepspeed").power_of_two is True
    assert get_strategy("megatron").power_of_two is False
    assert get_strategy("static", degree=4).degree == 4
    faithful = get_strategy("dhp-faithful")
    assert faithful.options["balance_packing"] is False
    assert faithful.options["serial_fallback"] is False


def test_register_new_strategy_is_one_entry():
    @register_strategy("all-ones-test")
    class AllOnes(Strategy):
        def _plan(self, seqs):
            from repro.core.scheduler import (ExecutionPlan, GroupPlan,
                                              MicroBatchPlan)
            groups = [GroupPlan([s.seq_id], 1,
                                self.cm.group_time([s], 1), s.length)
                      for s in seqs]
            mk = max(g.est_time for g in groups)
            return ExecutionPlan([MicroBatchPlan(groups, mk, len(groups))],
                                 mk, 0.0, 0.0)

    strat = get_strategy("all-ones-test").bind(CM, 8, 1e4)
    plan = strat.plan(_seqs([100, 200]))
    assert plan.strategy_name == "all-ones-test"
    assert plan.degree_histogram == {1: 2}


# ------------------------------------------------------------ planning
def test_every_builtin_strategy_plans_and_is_attributed():
    seqs = _seqs([128, 400, 900, 1500, 300, 64])
    for name in available_strategies():
        if name in ("oracle", "all-ones-test"):
            continue
        plan = get_strategy(name).bind(CM, 8, 2000.0).plan(seqs)
        assert plan.strategy_name == name
        scheduled = {i for mb in plan.micro_batches for g in mb.groups
                     for i in g.seq_ids}
        assert scheduled == {s.seq_id for s in seqs}, name
        assert plan.stage_ms, name


def test_dhp_stage_timings_cover_pipeline():
    plan = get_strategy("dhp").bind(CM, 8, 2000.0).plan(
        _seqs([128, 400, 900, 1500]))
    assert {"microbatch", "pack", "allocate"} <= set(plan.stage_ms)
    assert all(v >= 0.0 for v in plan.stage_ms.values())


def test_bruteforce_is_exact_lower_bound_on_makespan():
    """The exhaustive Stage-2 solver can never produce a worse makespan
    than the DP on the same packing."""
    seqs = _seqs([500, 1200, 800])
    dp = get_strategy("dhp", serial_fallback=False).bind(
        CM, 6, 1500.0).plan(seqs)
    bf = get_strategy("bruteforce").bind(CM, 6, 1500.0).plan(seqs)
    assert bf.total_time_est <= dp.total_time_est * (1 + 1e-9)


def test_async_prepare_collect_uniform_across_strategies():
    seqs = _seqs([128, 700, 2100])
    for name in ("static", "dhp"):
        strat = get_strategy(name).bind(CM, 8, 2500.0)
        strat.prepare(seqs)
        plan = strat.collect()
        assert plan.strategy_name == name
        with pytest.raises(RuntimeError):
            strat.collect()        # second collect without prepare
        strat.close()


def test_unbound_strategy_raises():
    with pytest.raises(RuntimeError, match="unbound"):
        get_strategy("dhp").plan(_seqs([100]))


# ------------------------------------------------------------ oracle
def test_measured_cost_model_prefers_measurements():
    mcm = MeasuredCostModel(CM)
    seqs = _seqs([1000])
    est = CM.group_time(seqs, 2)
    assert mcm.group_time(seqs, 2) == pytest.approx(est)
    mcm.record(tokens=1000, degree=2, seconds=42.0)
    assert mcm.group_time(seqs, 2) == pytest.approx(42.0)
    # unmeasured shapes get the calibration-scaled analytic estimate
    other = _seqs([8000])
    scaled = mcm.group_time(other, 4)
    assert scaled == pytest.approx(
        CM.group_time(other, 4) * (42.0 / CM.group_time(seqs, 2)))


def test_oracle_observe_skips_compile_tainted_samples():
    strat = get_strategy("oracle").bind(CM, 8, 2000.0)
    strat.observe(None, [
        {"tokens": 500, "degree": 1, "seconds": 9.0, "compiled": True},
        {"tokens": 500, "degree": 1, "seconds": 0.5, "compiled": False},
    ])
    assert strat.measured.n_samples == 1
    assert strat.measured.group_time(_seqs([500]), 1) == pytest.approx(0.5)


def test_oracle_plan_cost_evaluates_any_plan():
    strat = get_strategy("oracle").bind(CM, 8, 2000.0)
    seqs = _seqs([300, 900])
    static = get_strategy("static").bind(CM, 8, 2000.0).plan(seqs)
    cost = strat.plan_cost(static, seqs)
    assert cost > 0


# ------------------------------------------------------------ engine
def test_engine_plan_host_side():
    """Planning needs no multi-device mesh — runs in-process."""
    eng = Engine("internvl3-2b", ClusterSpec.auto(mem_budget=900.0),
                 strategy="dhp", reduced=True)
    from repro.data.pipeline import HeterogeneousLoader
    data = next(iter(HeterogeneousLoader(
        "openvid", 8, eng.cfg.vocab, seed=2, max_tokens=512,
        tokens_per_frame=16)))
    plan = eng.plan(data)
    assert plan.strategy_name == "dhp"
    assert plan.n_groups >= 1
    assert eng.cfg.family == "dense"       # vlm normalised to tokens
    assert Session is Engine


def test_engine_train_execute_smoke_8_devices(subproc):
    """Engine.plan/execute/train on the 8-host-device CPU demo mesh:
    dhp and static run through the SAME loop; oracle learns
    measurements."""
    subproc("""
from repro.api import ClusterSpec, Engine
cluster = ClusterSpec.auto(mem_budget=900.0)

eng = Engine("internvl3-2b", cluster, strategy="dhp", reduced=True,
             seed=3)
hist = eng.train(steps=4, dataset="openvid", global_batch=12,
                 max_tokens=512)
assert len(hist) == 4
assert all(m.strategy == "dhp" for m in hist)
degrees = set()
for m in hist:
    degrees.update(m.degree_histogram)
assert len(degrees) >= 2, degrees          # heterogeneous CP degrees
assert hist[-1].loss < hist[0].loss + 0.5  # sane loss trajectory

static = Engine("internvl3-2b", cluster, strategy="static",
                reduced=True, seed=3)
h2 = static.train(steps=2, dataset="openvid", global_batch=12,
                  max_tokens=512)
assert all(m.strategy == "static" for m in h2)

oracle = Engine("internvl3-2b", cluster, strategy="oracle",
                reduced=True, seed=3)
h3 = oracle.train(steps=3, dataset="openvid", global_batch=8,
                  max_tokens=512)
assert oracle.strategy.measured.n_samples > 0
print("ok", hist[0].loss, "->", hist[-1].loss,
      "oracle samples", oracle.strategy.measured.n_samples)
""", n_devices=8)


# ------------------------------------------------------------ compat
def test_backward_compat_core_import_surface():
    from repro.core import (Allocation, AtomicGroup, CostCoeffs,  # noqa
                            CostModel, DHPScheduler, ExecutionPlan,
                            GroupPlan, Hardware, MicroBatchPlan,
                            Profiler, SeqInfo, allocate,
                            allocate_bruteforce, analytic_coeffs,
                            pack_sequences, static_plan)
    # pre-API positional construction still works (new fields default)
    plan = ExecutionPlan([], 0.0, 0.0, 0.0)
    assert plan.strategy_name == "" and plan.stage_ms == {}


def test_backward_compat_launch_train_shims():
    from repro.launch.train import (build_parser, main,  # noqa: F401
                                    run_dhp, run_static)
    args = build_parser().parse_args(["--mode", "dhp", "--steps", "1"])
    assert (args.strategy or args.mode) == "dhp"


def test_cli_list_strategies(capsys):
    from repro.api.cli import main
    main(["--list-strategies"])
    out = capsys.readouterr().out.split()
    for name in ("static", "dhp", "bruteforce", "oracle"):
        assert name in out


def test_use_compile_cache_placement(monkeypatch):
    """Entry points keep the compile cache where JAX_COMPILATION_CACHE_DIR
    says, else at one fixed, git-ignored path inside the checkout."""
    from pathlib import Path

    import jax
    from repro.api import cli
    root = Path(__file__).resolve().parents[1]
    assert cli.COMPILE_CACHE_DIR == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        cli.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        cli.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(
            cli.COMPILE_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
