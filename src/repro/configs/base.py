"""Model / run configuration system.

One frozen dataclass describes every architecture in the assigned pool;
family-specific knobs live in optional sub-fields. `ModelConfig.reduced()`
derives the CPU smoke-test variant (2 layers, d_model <= 512, <= 4
experts held; 3 layers where a dense layer leads the expert layers)
required per architecture.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoECfg:
    n_experts: int
    top_k: int
    expert_ff: int            # d_ff of each expert
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    dispatch: str = "einsum"  # einsum (one-hot baseline) | sort (O(T·k·D))
    dispatch_group: int = 8192  # sort: tokens per shard-local dispatch
                                # group (0 = one global group)
    # -- the dropless expert layer (scoring="sigmoid", DeepSeek-V3's
    # -- noaux_tc): it holds `n_experts` of the router's `router_experts`,
    # -- from `expert_offset` on, and computes their part for every pair
    # -- routed to them; the capacity fields above do not apply
    router_experts: Optional[int] = None  # router width; None: n_experts
    expert_offset: int = 0    # index of the first expert held here
    n_shared: int = 0         # shared experts: one SwiGLU n_shared x wide
    scoring: str = "softmax"  # softmax (capacity) | sigmoid (dropless)
    route_scale: float = 1.0  # routed_scaling_factor
    first_dense: int = 0      # leading dense layers (d_ff wide)

    @property
    def n_routed(self) -> int:
        """Experts the router scores (all of them, held here or not)."""
        return self.router_experts or self.n_experts

    @property
    def dropless(self) -> bool:
        return self.scoring == "sigmoid"


@dataclasses.dataclass(frozen=True)
class MLACfg:
    """DeepSeek-V2 multi-head latent attention with a full q projection
    (`q_lora_rank` null): K and V come from one `kv_lora_rank` latent
    (RMS-normed) and a rope key shared by all heads."""
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class SSMCfg:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2           # d_inner = expand * d_model
    chunk: int = 256          # SSD chunk length
    conv_width: int = 4
    dt_min: float = 1e-3
    dt_max: float = 0.1


@dataclasses.dataclass(frozen=True)
class HybridCfg:
    # RecurrentGemma / Griffin: pattern unit (rec, rec, attn)
    pattern: Tuple[str, ...] = ("rec", "rec", "attn")
    lru_width: Optional[int] = None   # defaults to d_model
    window: int = 2048                # local attention window
    conv_width: int = 4


@dataclasses.dataclass(frozen=True)
class EncDecCfg:
    """Whisper-style encoder-decoder; encoder consumes stub frame embeds."""
    n_enc_layers: int = 12
    n_audio_frames: int = 1500        # conv-frontend output length (stub)


@dataclasses.dataclass(frozen=True)
class VLMCfg:
    """Pixtral-style VLM; ViT frontend is a stub providing patch embeds."""
    vision_dim: int = 1024            # stub patch-embedding dim
    patches_per_seq_frac: float = 0.25  # fraction of seq positions = image


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    family: str               # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    source: str = ""          # paper / model-card citation

    head_dim: Optional[int] = None      # default d_model // n_heads
    rope_theta: float = 500_000.0
    rope_2d: bool = False               # chatglm3 partial-rotary style
    use_rope: bool = True               # False: absolute sinusoidal (whisper)
    norm_eps: float = 1e-5
    activation: str = "swiglu"          # swiglu | gelu
    tie_embeddings: bool = False

    moe: Optional[MoECfg] = None
    mla: Optional[MLACfg] = None
    ssm: Optional[SSMCfg] = None
    hybrid: Optional[HybridCfg] = None
    encdec: Optional[EncDecCfg] = None
    vlm: Optional[VLMCfg] = None

    # attention behaviour
    sliding_window: Optional[int] = None    # sub-quadratic variant (decode)
    attn_impl: str = "chunked"              # reference | chunked | pallas
    cp_axis: Optional[str] = None           # ring-CP mesh axis (shard_map)
    remat: bool = True                      # activation checkpoint per layer
    param_dtype: str = "bfloat16"
    scan_layers: bool = True

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def is_decode_capable(self) -> bool:
        return True   # all assigned archs have a decoder

    def sub_quadratic(self) -> bool:
        """Can this arch decode a 500k context without O(L) full KV attn?"""
        return (self.family in ("ssm", "hybrid")
                or self.sliding_window is not None)

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: 2 layers, d_model<=512, <=4 experts."""
        d_model = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4)
        kv = max(1, min(self.kv_heads, n_heads))
        while n_heads % kv:
            kv -= 1
        kw = dict(
            n_layers=(3 if self.family == "hybrid"
                      else 2 + min(self.moe.first_dense, 1) if self.moe
                      else 2),
            d_model=d_model,
            n_heads=n_heads,
            kv_heads=kv,
            head_dim=None,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab=min(self.vocab, 1024),
            param_dtype="float32",
            attn_impl="reference",
            remat=False,
        )
        if self.moe:
            kw["moe"] = dataclasses.replace(
                self.moe, n_experts=4, top_k=min(self.moe.top_k, 2),
                expert_ff=min(self.moe.expert_ff, 256),
                # the dropless layer holds a real share: 4 of 8
                router_experts=8 if self.moe.dropless else None,
                expert_offset=0, first_dense=min(self.moe.first_dense, 1))
        if self.mla:
            m = self.mla
            kw["mla"] = dataclasses.replace(
                m, kv_lora_rank=min(m.kv_lora_rank, 64),
                qk_nope_head_dim=min(m.qk_nope_head_dim, 32),
                qk_rope_head_dim=min(m.qk_rope_head_dim, 16),
                v_head_dim=min(m.v_head_dim, 32))
        if self.ssm:
            kw["ssm"] = dataclasses.replace(
                self.ssm, d_state=16, head_dim=32, chunk=32)
        if self.hybrid:
            kw["hybrid"] = dataclasses.replace(
                self.hybrid, lru_width=d_model, window=64)
        if self.encdec:
            kw["encdec"] = dataclasses.replace(
                self.encdec, n_enc_layers=2, n_audio_frames=16)
        if self.vlm:
            kw["vlm"] = dataclasses.replace(self.vlm, vision_dim=64)
        return self.with_(**kw)


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                 # train | prefill | decode


TRAIN_4K = InputShape("train_4k", 4_096, 256, "train")
PREFILL_32K = InputShape("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = InputShape("decode_32k", 32_768, 128, "decode")
LONG_500K = InputShape("long_500k", 524_288, 1, "decode")

INPUT_SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K,
                                    LONG_500K)}
