"""Weights from a seed, in the parameter layout the program trains.

The benchmark makes the weights itself, on the device, in one jitted
call, in the dtype the configuration states: the program is handed
them, and the reference makes the same ones again from the same seed.
The layout is the model module's `shapes(cfg)` (`models/<model>.py`):
a tree of dicts whose leaves are shapes, one key of the seed each, in
the tree's flattened order.

Matrices are normal with standard deviation 1/sqrt(fan_in), the
embedding 0.02, norm scales one. A model module may set the rule of a
leaf of its own in `INIT`, a dict from the leaf's name (`leaf_names`)
to `rule(key, shape, dtype)`.
"""
from __future__ import annotations

import functools
import json
from typing import Dict

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def _name(path) -> str:
    return "/".join(str(p.key) for p in path)


@functools.lru_cache(maxsize=8)
def _initializer(model, cfg_json: str):
    cfg = json.loads(cfg_json)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        model.shapes(cfg), is_leaf=_is_shape)
    dtype = DTYPES[cfg["param_dtype"]]
    rules = getattr(model, "INIT", {})

    def leaf(key, path, shape):
        if _name(path) in rules:
            return rules[_name(path)](key, shape, dtype)
        name = path[-1].key
        if name == "scale":
            return jnp.ones(shape, dtype)
        std = 0.02 if name == "embed" else shape[-2] ** -0.5
        return (jax.random.normal(key, shape, jnp.float32) * std
                ).astype(dtype)

    def init(key):
        keys = jax.random.split(key, len(paths))
        return jax.tree_util.tree_unflatten(
            treedef, [leaf(k, p, s) for k, (p, s) in zip(keys, paths)])

    return jax.jit(init)


def make_params(model, cfg: Dict, raw_key, device=None):
    """The configuration's weights from `raw_key` (uint32[2]), in
    `cfg["param_dtype"]`, made on `device` in one jitted call."""
    key = jax.device_put(jnp.asarray(raw_key, jnp.uint32), device)
    return _initializer(model, json.dumps(cfg, sort_keys=True))(key)


def leaf_names(tree) -> list:
    return [_name(path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
