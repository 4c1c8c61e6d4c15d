"""Loss + train step factory."""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..models.model import forward
from .optimizer import AdamW, AdamWState, clip_by_global_norm


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState


def token_nll(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-position next-token NLL in float32, no masking applied:
    logits [..., V], labels [...] -> [...].

    The gold logit is a compare-select-reduce against a vocabulary iota,
    not `take_along_axis`. A gather's gradient is a scatter, and XLA runs
    it on the flattened logits: where V is not a multiple of 128 the flat
    layout differs from the tiled one, and each way costs a relayout loop
    over the whole [.., V] buffer. The select's gradient is elementwise
    and fuses into the softmax gradient. Under GSPMD it also keeps a
    vocab-sharded V sharded, where a gather would all-gather the logits.
    A sum of exact zeros and one value is that value, so the NLL equals
    the gather's bit for bit."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    vocab = jax.lax.broadcasted_iota(labels.dtype, logits.shape,
                                     logits.ndim - 1)
    gold = jnp.sum(jnp.where(vocab == labels[..., None], logits, 0.0),
                   axis=-1)
    return logz - gold


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token NLL (`token_nll`). logits [B,S,V], labels [B,S]."""
    nll = token_nll(logits, labels)
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, Any]):
    logits, aux = forward(params, cfg, batch)
    aux_coef = cfg.moe.aux_loss_coef if cfg.moe else 0.0
    loss = cross_entropy(logits, batch["labels"], batch.get("mask"))
    return loss + aux_coef * aux / max(cfg.n_layers, 1), (loss, aux)


def make_train_step(cfg: ModelConfig, opt: AdamW, *,
                    grad_clip: float = 1.0, dp_axis: Optional[str] = None,
                    accum_steps: int = 1, grad_constraint=None):
    """Returns train_step(state, batch) -> (state, metrics).

    `dp_axis` psums grads (used inside shard_map CP/DP groups); under
    plain pjit the partitioner inserts the reduction automatically.
    `accum_steps > 1` splits the global batch along its leading axis into
    micro-batches processed by a lax.scan (gradient accumulation): the
    peak activation footprint shrinks by ~accum_steps at the cost of one
    extra grads-sized buffer — the standard fit for llama3-405b-class
    training steps (see DESIGN.md / §Perf).

    `grad_constraint`: optional grads_tree -> grads_tree hook applying
    `with_sharding_constraint`s to the accumulator carry. Constraining
    the carry to the FSDP param sharding makes GSPMD reduce-scatter each
    micro-batch's gradient instead of all-reducing it and carrying a
    replicated f32 accumulator (§Perf iteration L1: 4× less gradient
    collective traffic and 1/data_ways the accumulator memory).
    """
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def one(params, batch):
        (total, (loss, aux)), grads = grad_fn(params, cfg, batch)
        return total, loss, aux, grads

    def train_step(state: TrainState, batch):
        if accum_steps == 1:
            total, loss, aux, grads = one(state.params, batch)
            if grad_constraint is not None:
                grads = grad_constraint(grads)
        else:
            def split(x):
                b = x.shape[0]
                assert b % accum_steps == 0, (b, accum_steps)
                return x.reshape(accum_steps, b // accum_steps,
                                 *x.shape[1:])
            micro = jax.tree.map(split, batch)
            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            if grad_constraint is not None:
                zero_g = grad_constraint(zero_g)
            zero_m = (jnp.zeros((), jnp.float32),) * 3

            def body(carry, mb):
                (t, l, a), g = carry
                ti, li, ai, gi = one(state.params, mb)
                g = jax.tree.map(
                    lambda x, y: x + y.astype(jnp.float32), g, gi)
                if grad_constraint is not None:
                    g = grad_constraint(g)
                return ((t + ti, l + li, a + ai), g), None

            ((total, loss, aux), grads), _ = jax.lax.scan(
                body, (zero_m, zero_g), micro)
            total, loss, aux = (x / accum_steps for x in
                                (total, loss, aux))
            grads = jax.tree.map(lambda g_, p: (g_ / accum_steps).astype(
                p.dtype), grads, state.params)
        if dp_axis is not None:
            grads = jax.lax.pmean(grads, dp_axis)
            loss = jax.lax.pmean(loss, dp_axis)
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        params, opt_state = opt.update(grads, state.opt, state.params)
        metrics = {"loss": loss, "aux": aux, "grad_norm": gnorm,
                   "total": total}
        return TrainState(params, opt_state), metrics

    return train_step


def make_eval_step(cfg: ModelConfig):
    def eval_step(params, batch):
        _, (loss, _aux) = loss_fn(params, cfg, batch)
        return loss
    return eval_step
