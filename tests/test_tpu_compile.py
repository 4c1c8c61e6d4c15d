"""Ahead-of-time compiles for a described TPU v5e chip.

Nothing runs here: each test lowers a program for one chip of a
described `v5e:2x2` topology and compiles it with the TPU compiler, so a
kernel Mosaic refuses, or a step that does not fit the chip's memory,
fails on a CPU-only machine. The topology is described inside a fixture
(never at import) because only one process at a time may load the TPU
library; all such tests live in this one file so that one worker holds
it.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core.executor import DHPExecutor
from repro.core.group_pool import GroupPool
from repro.kernels.flash_attention import (flash_attention_flat,
                                           flash_attention_packed_flat)
from repro.models.model import init_params

#: internvl3-2b attention at the bring-up's bucket: 12 query heads of
#: width 128 (the kernels take KV already expanded to every head)
BH, S, D = 12, 4096, 128
HBM_BYTES = 16 * 2**30          # one v5e chip


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


def test_executor_grad_step_fits_one_chip(topo):
    """The executor's span-bearing packed grad step at internvl3-2b
    widths (one layer, 2048-token bucket) on a one-chip group mesh."""
    cfg = get_config("internvl3-2b").with_(family="dense", vlm=None,
                                           n_layers=1)
    ex = DHPExecutor(cfg, pool=GroupPool(topo.devices[:1]))
    step, _, _ = ex._packed_grad_fn(0, 1, 2048, with_spans=True)
    mesh = ex.pool.mesh_for(0, 1)
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda a: _shape(a.shape, a.dtype, rep),
        jax.eval_shape(lambda k: init_params(k, cfg),
                       jax.random.PRNGKey(0)))
    seq = NamedSharding(mesh, P(None, "cp"))
    batch = {k: _shape((1, 2048), np.dtype(dt), seq) for k, dt in (
        ("tokens", "int32"), ("labels", "int32"), ("mask", "float32"),
        ("positions", "int32"), ("modality_ids", "int32"),
        ("loss_mask", "float32"), ("modality_classes", "int32"),
        ("segment_ids", "int32"))}
    mem = step.lower(params, batch).compile().memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert live < HBM_BYTES, live
