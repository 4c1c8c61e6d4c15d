"""Kimi-VL-A3B-Instruct's language model: a DeepSeek-V3-style decoder of
27 layers, d_model 2048 — MLA (16 heads, kv_lora_rank 512, q/k head
128 + 64 rope, v head 128, full q projection), one leading dense layer
(FFN 11264), then 26 layers of 64 routed experts (width 1408, sigmoid
scores, top 6 chosen by score + correction bias, weights normalised and
times 2.446) beside 2 shared experts. Vision tower not run (token-stream
mode)."""
from .base import MLACfg, ModelConfig, MoECfg

CONFIG = ModelConfig(
    arch_id="kimi-vl-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, kv_heads=16,
    d_ff=11264, vocab=163840,
    rope_theta=800_000.0, norm_eps=1e-5,
    mla=MLACfg(kv_lora_rank=512, qk_nope_head_dim=128,
               qk_rope_head_dim=64, v_head_dim=128),
    moe=MoECfg(n_experts=64, top_k=6, expert_ff=1408, n_shared=2,
               scoring="sigmoid", route_scale=2.446, first_dense=1),
    source="https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/"
           "blob/main/config.json",
)
