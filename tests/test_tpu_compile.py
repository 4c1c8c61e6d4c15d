"""Ahead-of-time compiles for a described TPU v5e chip.

Nothing runs here: each test lowers a program for one chip of a
described `v5e:2x2` topology and compiles it with the TPU compiler, so a
kernel Mosaic refuses, or a step that does not fit the chip's memory,
fails on a CPU-only machine. The topology is described inside a fixture
(never at import) because only one process at a time may load the TPU
library; all such tests live in this one file so that one worker holds
it.
"""
import dataclasses
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core.executor import DHPExecutor, accumulate_grads
from repro.core.group_pool import GroupPool
from repro.core.packing import MODALITY_CLASSES
from repro.kernels.flash_attention import (flash_attention_flat,
                                           flash_attention_packed_flat)
from repro.models.model import init_params

#: internvl3-2b attention at the bring-up's bucket: 12 query heads of
#: width 128 (the kernels take KV already expanded to every head)
BH, S, D = 12, 4096, 128
HBM_BYTES = 16 * 2**30          # one v5e chip
#: the bucket of the internvl3-2b grad-step compiles
GRAD_BUCKET = 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache off
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_flat_compiles(one_chip):
    qkv = [_shape((BH, S, D), jnp.bfloat16, one_chip)] * 3
    fn = jax.jit(lambda q, k, v: flash_attention_flat(
        q, k, v, mode="causal", interpret=False))
    _assert_kernel(fn.lower(*qkv).compile())


@pytest.mark.parametrize("with_spans", [False, True],
                         ids=["segments", "segments+spans"])
def test_flash_attention_packed_flat_compiles(one_chip, with_spans):
    qkv = [_shape((BH, S, D), jnp.bfloat16, one_chip)] * 3
    seg = _shape((BH, S), jnp.int32, one_chip)
    if with_spans:
        fn = jax.jit(lambda q, k, v, s, sp: flash_attention_packed_flat(
            q, k, v, s, span_ids=sp, mode="causal", interpret=False))
        lowered = fn.lower(*qkv, seg, seg)
    else:
        fn = jax.jit(lambda q, k, v, s: flash_attention_packed_flat(
            q, k, v, s, mode="causal", interpret=False))
        lowered = fn.lower(*qkv, seg)
    _assert_kernel(lowered.compile())


def _dense_grad_step(topo, with_spans):
    """The executor's packed grad step at internvl3-2b widths (one layer,
    151674 vocabulary rows), compiled for a one-chip group mesh."""
    cfg = get_config("internvl3-2b").with_(family="dense", vlm=None,
                                           n_layers=1)
    ex = DHPExecutor(cfg, pool=GroupPool(topo.devices[:1]))
    step, _, _ = ex._packed_grad_fn(0, 1, GRAD_BUCKET,
                                    with_spans=with_spans)
    mesh = ex.pool.mesh_for(0, 1)
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda a: _shape(a.shape, a.dtype, rep),
        jax.eval_shape(lambda k: init_params(k, cfg),
                       jax.random.PRNGKey(0)))
    seq = NamedSharding(mesh, P(None, "cp"))
    keys = [("tokens", "int32"), ("labels", "int32"), ("mask", "float32"),
            ("positions", "int32")]
    if with_spans:
        keys += [("modality_ids", "int32"), ("loss_mask", "float32"),
                 ("modality_classes", "int32")]
    batch = {k: _shape((1, GRAD_BUCKET), np.dtype(dt), seq)
             for k, dt in keys + [("segment_ids", "int32")]}
    return cfg, step.lower(params, batch).compile()


def test_executor_grad_step_fits_one_chip(topo):
    """The executor's span-bearing packed grad step at internvl3-2b
    widths (one layer, 2048-token bucket) on a one-chip group mesh."""
    _, compiled = _dense_grad_step(topo, with_spans=True)
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert live < HBM_BYTES, live


@pytest.mark.parametrize("with_spans", [False, True],
                         ids=["causal", "spans"])
def test_grad_step_keeps_the_logits_tiled(topo, with_spans):
    """At a vocabulary that is not a multiple of 128 (151674), the
    compiled grad step holds no flat [bucket x V] buffer and no loop
    over the logits: a gather's scatter gradient ran on the flattened
    logits, with a relayout loop each way between flat and tiled."""
    cfg, compiled = _dense_grad_step(topo, with_spans)
    text = compiled.as_text()
    assert cfg.vocab % 128
    assert not re.search(r"\w+\[%d\]" % (GRAD_BUCKET * cfg.vocab), text)
    loops = [line for line in text.splitlines() if " while(" in line]
    assert not [line for line in loops
                if f"{GRAD_BUCKET},{cfg.vocab}]" in line], loops


def test_kimi_grad_step_fits_one_chip(topo, monkeypatch):
    """The executor's span-bearing packed grad step for kimi-vl-a3b-d6e8
    (MLA; 1 dense + 5 expert layers holding 8 of 64 experts, the grouped
    matmuls as Pallas kernels; 20480 vocabulary rows) at a 4096-token
    bucket on a one-chip group mesh: it compiles, and with the state it
    trains beside it (float32 moments and gradient accumulator) it fits
    the chip."""
    import repro.kernels
    # this process's backend is the CPU: compile the kernels, do not
    # interpret them
    monkeypatch.setattr(repro.kernels, "interpret_mode",
                        lambda interpret=None: False)
    base = get_config("kimi-vl-a3b")
    cfg = base.with_(n_layers=6, vocab=20480, moe=dataclasses.replace(
        base.moe, n_experts=8, router_experts=64))
    ex = DHPExecutor(cfg, pool=GroupPool(topo.devices[:1]))
    step, _, _ = ex._packed_grad_fn(0, 1, 4096, with_spans=True)
    mesh = ex.pool.mesh_for(0, 1)
    rep = NamedSharding(mesh, P())
    tree = jax.eval_shape(lambda k: init_params(k, cfg),
                          jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _shape(a.shape, a.dtype, rep), tree)
    seq = NamedSharding(mesh, P(None, "cp"))
    batch = {k: _shape((1, 4096), np.dtype(dt), seq) for k, dt in (
        ("tokens", "int32"), ("labels", "int32"), ("mask", "float32"),
        ("positions", "int32"), ("modality_ids", "int32"),
        ("loss_mask", "float32"), ("modality_classes", "int32"),
        ("segment_ids", "int32"))}
    compiled = step.lower(params, batch).compile()
    kernels = re.findall(r"%(t?gmm)\.\d+ = ", compiled.as_text())
    # 3 projections: forward, again under remat, gmm for dx, tgmm for dW
    assert sorted(kernels) == ["gmm"] * 9 + ["tgmm"] * 3
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    state = 12 * sum(a.size for a in jax.tree.leaves(tree))
    assert live + state < HBM_BYTES, (live, state)


@pytest.mark.parametrize("last", [False, True], ids=["add", "add+divide"])
def test_accumulate_grads_updates_in_place(one_chip, last):
    """The executor's float32 gradient accumulator at the benchmark's
    internvl3-2b widths (4 layers): the donated accumulator's buffers
    become the result's, so a group's add needs no second copy."""
    cfg = get_config("internvl3-2b").with_(family="dense", vlm=None,
                                           n_layers=4)
    tree = jax.eval_shape(lambda k: init_params(k, cfg),
                          jax.random.PRNGKey(0))
    aux = _shape((len(MODALITY_CLASSES), 2), jnp.float32, one_chip)
    scalar = _shape((), jnp.float32, one_chip)
    acc = (scalar, jax.tree.map(
        lambda a: _shape(a.shape, jnp.float32, one_chip), tree), aux)
    out = (scalar, jax.tree.map(
        lambda a: _shape(a.shape, a.dtype, one_chip), tree), aux)
    total = scalar if last else None
    mem = accumulate_grads.lower(acc, out, scalar, total).compile(
        ).memory_analysis()
    f32_bytes = sum(4 * a.size for a in jax.tree.leaves(tree))
    assert mem.alias_size_in_bytes >= f32_bytes, mem
    assert mem.temp_size_in_bytes < f32_bytes // 100, mem
