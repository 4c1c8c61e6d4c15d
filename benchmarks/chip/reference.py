"""Plain float32 reference of the training steps the benchmark checks.

The model is the configuration's model module (`models/<model>.py`):
its `row_loss(p, row, cfg, mm)` is the forward pass and summed
next-token NLL of one packed row, written from the configuration alone
(it imports nothing of the program). Here, once for every model: the
step's loss is the mean over its labelled tokens; AdamW with decoupled
weight decay on every leaf but the norm scales. Every matmul runs at
`Precision.HIGHEST`; parameters are stored between steps in the
configuration's dtype (bf16 parameters are rounded after each update,
as a bf16-parameter trainer must), all arithmetic is float32.

It runs in blocks: a step's sequences are packed first-fit into rows
of `row_tokens`, one row's gradient at a time is added into a float32
accumulator. The optimizer's moments wait on the host between steps,
so that the parameters, the accumulator and one row's gradient fit
beside the activations on one chip.

`precision="fp8"` is the control: the same steps with every matmul
operand rounded to 8-bit floats (4 exponent and 3 mantissa bits, scaled
per tensor) and every gradient that reaches a matmul operand rounded to
5 exponent and 2 mantissa bits — the step below the configuration's
bf16 that would tempt a later change.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import checks
import model_init
import traffic

HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# float8 control: fake quantisation with a scale per tensor
# --------------------------------------------------------------------------
def round_to(x, dtype):
    """Round float32 `x` to `dtype`'s precision, kept in float32.

    `lax.reduce_precision`, not a round trip through `astype`: XLA may
    drop a float32 -> bfloat16 -> float32 pair of converts (it allows
    excess precision by default), and then nothing is rounded."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _round_fp8(x, exponent_bits, mantissa_bits):
    """Scale the tensor's largest magnitude to the format's largest
    finite value, round every element to the format, scale back."""
    fmax = 2.0 ** (2 ** (exponent_bits - 1) - 1) * (2 - 2.0 ** -mantissa_bits)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / fmax, 1.0)
    return jax.lax.reduce_precision(x / scale, exponent_bits=exponent_bits,
                                    mantissa_bits=mantissa_bits) * scale


@jax.custom_vjp
def _q8(x):
    return _round_fp8(x, 4, 3)          # e4m3


def _q8_fwd(x):
    return _q8(x), None


def _q8_bwd(_, g):
    return (_round_fp8(g, 5, 2),)       # e5m2


_q8.defvjp(_q8_fwd, _q8_bwd)


def _matmul(precision: str):
    if precision == "fp32":
        return lambda a, b: jnp.matmul(a, b, precision=HIGHEST)
    if precision == "fp8":
        return lambda a, b: jnp.matmul(_q8(a), _q8(b), precision=HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


# --------------------------------------------------------------------------
# rows: a step's sequences packed first-fit into fixed-length rows
# --------------------------------------------------------------------------
def pack_rows(seqs: Sequence[traffic.Seq], toks: Sequence[np.ndarray],
              row_tokens: int) -> List[Dict[str, np.ndarray]]:
    """The rows a model's `row_loss` reads, each of `row_tokens`:
    `tokens`; `labels`, the next token; `lmask`, 1 where the label
    counts (its token is not inside a bidirectional block); `pos`, the
    position within the sequence; `seg`, the sequence's index in the row
    (-1: padding); `block`, the bidirectional block's index in the row
    (-1: causal)."""
    bins: List[List[int]] = []
    used: List[int] = []
    for i, s in enumerate(seqs):
        n = traffic.seq_len(s)
        if n > row_tokens:
            raise ValueError(f"sequence of {n} tokens > row {row_tokens}")
        for b in range(len(bins)):
            if used[b] + n <= row_tokens:
                bins[b].append(i)
                used[b] += n
                break
        else:
            bins.append([i])
            used.append(n)
    rows = []
    for members in bins:
        r = {"tokens": np.zeros(row_tokens, np.int32),
             "labels": np.zeros(row_tokens, np.int32),
             "lmask": np.zeros(row_tokens, np.float32),
             "pos": np.zeros(row_tokens, np.int32),
             "seg": np.full(row_tokens, -1, np.int32),
             "block": np.full(row_tokens, -1, np.int32)}
        off = 0
        n_blocks = 0
        for j, i in enumerate(members):
            s, t = seqs[i], toks[i]
            n = len(t)
            r["tokens"][off:off + n] = t
            r["labels"][off:off + n - 1] = t[1:]
            r["pos"][off:off + n] = np.arange(n)
            r["seg"][off:off + n] = j
            bidir = np.zeros(n, bool)
            for start, ln, b in s:
                if b:
                    r["block"][off + start:off + start + ln] = n_blocks
                    n_blocks += 1
                    bidir[start:start + ln] = True
            # position p predicts token p+1: labelled unless that token
            # sits inside a bidirectional block (it attends its future)
            r["lmask"][off:off + n - 1] = ~bidir[1:]
            off += n
        rows.append(r)
    return rows


@functools.lru_cache(maxsize=4)
def _programs(model, cfg_json: str, precision: str, opt_items: tuple):
    cfg, opt = json.loads(cfg_json), dict(opt_items)
    mm = _matmul(precision)
    storage = model_init.DTYPES[cfg["param_dtype"]]

    @jax.jit
    def row_grad(p, row):
        return jax.value_and_grad(
            lambda p: model.row_loss(p, row, cfg, mm))(p)

    @functools.partial(jax.jit, donate_argnums=0)
    def add(acc, g):
        return jax.tree.map(jnp.add, acc, g)

    @functools.partial(jax.jit, donate_argnums=0)
    def scale(acc, s):
        return jax.tree.map(lambda a: a * s, acc)

    @functools.partial(jax.jit, static_argnums=5, donate_argnums=(0, 2, 3))
    def adam_leaf(p, g, m, v, step, decay):
        b1, b2 = opt["b1"], opt["b2"]
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** step)
        vhat = v / (1 - b2 ** step)
        delta = mhat / (jnp.sqrt(vhat) + opt["eps"])
        if decay:
            delta = delta + opt["weight_decay"] * p
        p = round_to(p - opt["lr"] * delta, storage)
        return p, m, v

    return row_grad, add, scale, adam_leaf


def run(model, cfg: Dict, mix: Dict, raw_key, steps, *, precision="fp32",
        device=None) -> Dict[str, list]:
    """Train the configuration's weights from `raw_key` through `steps`
    (a list of (seqs, toks) per step) with the model module `model`, and
    read what the benchmark compares: each step's loss, the first step's
    gradient norm per leaf, and each leaf's change over all the steps."""
    row_grad, add, scale, adam_leaf = _programs(
        model, json.dumps(cfg, sort_keys=True), precision,
        tuple(sorted(mix["optimizer"].items())))
    device = device or jax.devices()[0]
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     model_init.make_params(model, cfg, raw_key, device))
    names = model_init.leaf_names(p)
    treedef = jax.tree.structure(p)
    moments = None                       # (m, v) per leaf, on the host
    out = {"losses": [], "grad_norms": None, "change_norms": None,
           "leaves": names}
    for k, (seqs, toks) in enumerate(steps):
        acc, total, count = None, 0.0, 0.0
        for row in pack_rows(seqs, toks, mix["max_seq_tokens"]):
            r = jax.device_put(row, device)
            s, g = row_grad(p, r)
            total += float(s)
            count += float(np.sum(row["lmask"]))
            acc = g if acc is None else add(acc, g)
            del g
        grad = scale(acc, jnp.float32(1.0 / count))
        out["losses"].append(total / count)
        if k == 0:
            out["grad_norms"] = np.asarray(checks.leaf_norms(grad)).tolist()
        leaves_p, leaves_g = jax.tree.leaves(p), jax.tree.leaves(grad)
        del p, grad, acc
        new_p, new_m = [], []
        for i, name in enumerate(names):
            pi, gi = leaves_p[i], leaves_g[i]
            leaves_p[i] = leaves_g[i] = None
            if moments is None:
                mi = jnp.zeros(pi.shape, jnp.float32, device=device)
                vi = jnp.zeros(pi.shape, jnp.float32, device=device)
            else:
                mi, vi = (jax.device_put(x, device) for x in moments[i])
            pi, mi, vi = adam_leaf(pi, gi, mi, vi, jnp.float32(k + 1),
                                   not name.endswith("scale"))
            new_p.append(pi)
            last = k == len(steps) - 1
            new_m.append(None if last else (np.asarray(mi), np.asarray(vi)))
            del gi, mi, vi
        moments = new_m
        p = jax.tree.unflatten(treedef, new_p)
    p0 = model_init.make_params(model, cfg, raw_key, device)
    out["change_norms"] = np.asarray(checks.diff_norms(p, p0)).tolist()
    return out
