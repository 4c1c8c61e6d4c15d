"""Pallas TPU kernels (flash attention, SSD chunk, RG-LRU scan), their
pure-jnp oracles (`ref.py`) and the model-layout wrappers (`ops.py`)."""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas call runs in the interpreter.

    `None` (every wrapper's default) decides from the backend: interpret
    only on the CPU backend, which has no Mosaic compiler. On a TPU the
    kernel compiles or raises; it never falls back silently. An explicit
    bool wins — an ahead-of-time compile for a described TPU from a CPU
    process passes `False`."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"
