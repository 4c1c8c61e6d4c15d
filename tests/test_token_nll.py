"""The per-token NLL (`training.train_step.token_nll`): the gold logit is
a one-hot select against a vocabulary iota, not a gather. It must equal
the `take_along_axis` form it replaced, in the NLL and in its gradient,
and the executor's grad step must hold no gather or scatter over the
logits, whose gradient XLA would run on the flattened buffer."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import executor as executor_mod
from repro.core.cost_model import ModalitySpan
from repro.core.executor import DHPExecutor
from repro.core.packing import flatten_group
from repro.models.model import init_params
from repro.training.train_step import token_nll

BUCKET = 256


def gather_nll(logits, labels):
    """The gold logit by `take_along_axis`, as the executor took it
    before the one-hot select."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - gold


def _logits_and_labels(vocab, seed=0):
    key = jax.random.PRNGKey(seed)
    logits = (3.0 * jax.random.normal(key, (1, BUCKET, vocab))
              ).astype(jnp.bfloat16)
    labels = jax.random.randint(jax.random.fold_in(key, 1), (1, BUCKET),
                                0, vocab)
    # the packing's pad id and the last vocabulary row
    labels = labels.at[0, :8].set(0).at[0, 8:16].set(vocab - 1)
    return logits, labels


@pytest.mark.parametrize("vocab", [1000, 1024])
def test_token_nll_equals_gather(vocab):
    """The NLL is bit-equal: a sum of exact zeros and one value is that
    value. The bf16 gradient of a weighted sum is bit-equal too (0 ulp;
    the select's transpose writes the same -ct at the gold position)."""
    logits, labels = _logits_and_labels(vocab)
    np.testing.assert_array_equal(np.asarray(token_nll(logits, labels)),
                                  np.asarray(gather_nll(logits, labels)))
    w = jax.random.uniform(jax.random.PRNGKey(7), (1, BUCKET))

    def grad_of(nll):
        return jax.jit(jax.grad(lambda x: (nll(x, labels) * w).sum()))(
            logits)

    new, old = grad_of(token_nll), grad_of(gather_nll)
    assert new.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(new, np.float32),
                                  np.asarray(old, np.float32))


# --------------------------------------------------------------------------
# the executor's grad step (reduced internvl3-2b, one device)
# --------------------------------------------------------------------------
def _cfg(vocab):
    return get_config("internvl3-2b").reduced().with_(
        family="dense", vlm=None, vocab=vocab)


def _batch(vocab, with_spans):
    rng = np.random.default_rng(5)
    lengths = (100, 90, 50)
    seqs = [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]
    seqs[0][1:9] = 0
    seqs[1][1:9] = vocab - 1
    spans = None
    if with_spans:
        spans = [(ModalitySpan("text", 0, 20),
                  ModalitySpan("vision", 20, 64, "bidirectional"),
                  ModalitySpan("text", 84, 16)),
                 None,
                 (ModalitySpan("vision", 0, 32, "bidirectional"),
                  ModalitySpan("text", 32, 18))]
    batch, _ = flatten_group(seqs, BUCKET, spans=spans)
    return {k: jnp.asarray(v) for k, v in batch.items()}


def _grad_step(cfg, with_spans):
    ex = DHPExecutor(cfg, devices=jax.devices()[:1])
    step, _, _ = ex._packed_grad_fn(0, 1, BUCKET, with_spans=with_spans)
    return step


@pytest.mark.parametrize("with_spans", [False, True],
                         ids=["causal", "spans"])
def test_grad_step_equals_gather(monkeypatch, with_spans):
    """The executor's loss and gradients with the one-hot select equal
    those of the gather form, bit for bit, on a vocabulary that is not a
    multiple of 128."""
    cfg = _cfg(1000)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg.vocab, with_spans)
    new = _grad_step(cfg, with_spans)(params, batch)
    monkeypatch.setattr(executor_mod, "token_nll", gather_nll)
    old = _grad_step(cfg, with_spans)(params, batch)
    assert np.isfinite(float(new[0])) and float(new[0]) > 0
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _logits_gather_or_scatter(text, vocab):
    """The gathers and scatters of the lowered program that take an
    operand of the logits' shape [1, BUCKET, vocab]: each op's text up to
    its type signature (a scatter's spans its update region's lines)."""
    shape = f"tensor<1x{BUCKET}x{vocab}x"
    ops = re.finditer(r'"stablehlo\.(gather|scatter)"\(.*?\) -> tensor<',
                      text, re.S)
    return [m.group(1) for m in ops if shape in m.group(0)]


@pytest.mark.parametrize("with_spans", [False, True],
                         ids=["causal", "spans"])
def test_grad_step_has_no_logits_gather_or_scatter(monkeypatch,
                                                   with_spans):
    """The lowered packed grad step has no gather or scatter over the
    [1, bucket, V] logits (V = 1000, not a multiple of 128); the
    embedding's own gather and scatter over the table stay. The gather
    form, lowered the same way, has both, so the check can see them."""
    cfg = _cfg(1000)
    params = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    batch = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         _batch(cfg.vocab, with_spans))

    def lowered():
        return _grad_step(cfg, with_spans).lower(params, batch).as_text()

    text = lowered()
    assert re.search(r"stablehlo\.gather", text)      # the embedding
    assert _logits_gather_or_scatter(text, cfg.vocab) == []
    monkeypatch.setattr(executor_mod, "token_nll", gather_nll)
    old = _logits_gather_or_scatter(lowered(), cfg.vocab)
    assert "gather" in old and "scatter" in old
