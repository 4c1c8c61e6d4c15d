"""The model-FLOP count: by hand on a tiny packed batch, and the same
whatever degree the planner gives the sequences."""
import pytest

import flops
import harness
import traffic

DENSE = harness.model_of({"model": "dense_gqa"})
CFG = {"n_layers": 2, "d_model": 8, "n_heads": 2, "kv_heads": 1,
       "head_dim": 4, "d_ff": 16, "vocab": 32, "activation": "swiglu"}


def test_hand_count():
    causal = ((0, 5, False),)                         # 5 text tokens
    mixed = ((0, 1, False), (1, 3, True), (4, 2, False))
    # pairs: causal 5*6/2 = 15; mixed 6*7/2 = 21 + the 3 forward pairs
    # of its 3-token bidirectional block
    assert traffic.pairs(causal) == 15
    assert traffic.pairs(mixed) == 24
    # matmul params per layer: q 8*8 + k 8*4 + v 8*4 + o 8*8 = 192,
    # SwiGLU 3*8*16 = 384; two layers and the 8x32 head
    per_token = 6 * (2 * (192 + 384) + 8 * 32)
    per_pair = 12 * 2 * 4 * 2
    assert DENSE.token_flops(CFG) == per_token
    assert flops.step_flops(DENSE, CFG, [causal, mixed]) == (
        11 * per_token + 39 * per_pair)


@pytest.mark.parametrize("ranks", [1, 2])
def test_same_count_at_any_degree(ranks):
    """Planned over 1 or 2 ranks with a budget that forces degree 2 on
    the longest sequence, the groups' counts add up to the batch's."""
    from repro.api import get_strategy
    from repro.api.engine import demo_cost_model
    from repro.configs import get_config

    mix = traffic.load(harness.HERE / "traffic" / "openvid.json")
    seqs, toks = traffic.step(mix, 1000, 4, 0)
    batch = harness.ragged(seqs, toks, "vision")
    longest = max(traffic.seq_len(s) for s in seqs)
    cm = demo_cost_model(get_config("internvl3-2b"))
    strat = get_strategy("dhp").bind(cm, ranks, float(
        longest if ranks == 1 else -(-longest // 2)))
    plan = strat.plan(batch.infos)
    degrees = [g.degree for mb in plan.micro_batches for g in mb.groups]
    assert max(degrees) == ranks
    planned = sum(flops.step_flops(DENSE, CFG,
                                   [seqs[i] for i in g.seq_ids])
                  for mb in plan.micro_batches for g in mb.groups)
    assert planned == flops.step_flops(DENSE, CFG, seqs)
