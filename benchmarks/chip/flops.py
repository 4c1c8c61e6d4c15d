"""Model FLOPs of a training step, from the configuration and the traffic.

The model module (`models/<model>.py`) counts per token
(`token_flops(cfg)`: the matmuls a token passes through, forward and
backward) and per attended (query, key) pair (`pair_flops(cfg)`, over
all layers). Attended pairs are those the mask allows inside one
sequence: causal, plus the forward pairs of each bidirectional block.
Pairs across packed sequences and padding are never counted, nor is
recomputation, so the count is the same however the planner groups the
sequences, and no kernel that skips masked tiles can push a share of
the peak above 1.
"""
from __future__ import annotations

from typing import Dict, Iterable

import traffic


def step_flops(model, cfg: Dict, seqs: Iterable[traffic.Seq]) -> int:
    seqs = list(seqs)
    tokens = sum(traffic.seq_len(s) for s in seqs)
    pairs = sum(traffic.pairs(s) for s in seqs)
    return tokens * model.token_flops(cfg) + pairs * model.pair_flops(cfg)
