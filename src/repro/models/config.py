"""Model configuration for the composable transformer stack.

One ModelConfig describes any of the registry's architectures: a cyclic
``block_pattern`` selects per-layer block kinds (attention global/local,
latent attention, RG-LRU, Mamba-1), with MoE substituting the MLP where
configured, after ``first_dense_layers`` leading layers that keep a dense
MLP.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

BLOCK_KINDS = ("attn", "attn_local", "mla", "rglru", "mamba")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None

    # layer pattern, cycled; e.g. gemma3: 5x local + 1 global
    block_pattern: Tuple[str, ...] = ("attn",)
    window: int = 4096              # local-attention window
    qk_norm: bool = False
    nonparametric_ln: bool = False  # olmo: LN without scale/bias
    mlp_act: str = "swiglu"         # swiglu | gelu
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    norm_eps: Optional[float] = None   # None: each norm's default (LayerNorm 1e-5, RMSNorm 1e-6)

    # multi-head latent attention ("mla" blocks; DeepSeek-V2/V3, no query
    # compression): keys and values come from one cached latent per token
    kv_lora_rank: int = 0           # width of the cached latent c_kv
    qk_nope_head_dim: int = 0       # per-head query/key width without rotary positions
    qk_rope_head_dim: int = 0       # rotary width, one key part shared by all heads
    v_head_dim: int = 0

    # MoE
    n_experts: int = 0
    top_k: int = 0
    expert_d_ff: int = 0            # per-expert hidden (granite 512, llama4 8192)
    shared_expert_d_ff: int = 0     # llama4 shared expert
    capacity_factor: float = 1.25
    moe_every: int = 1              # MoE replaces MLP every k-th layer
    first_dense_layers: int = 0     # leading layers that keep the dense MLP (width d_ff)
    moe_group: int = 1024           # tokens per group: training's capacity, inference's chunk
    # routing: softmax or sigmoid scores; with ``router_bias`` a learned bias
    # is added to the scores to choose the top_k experts, never to their gates
    router_score: str = "softmax"   # softmax | sigmoid
    router_bias: bool = False
    routed_scale: float = 1.0       # scale of the chosen gates, renormalised to sum to 1
    # the experts held here: the router scores all n_experts, this layer
    # holds experts [expert_offset, expert_offset + experts_held) and gives
    # their part of the result (expert parallelism without the exchange)
    experts_held: int = 0           # 0: all of them
    expert_offset: int = 0

    # Mamba-1
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    dt_rank: int = 0                # 0 -> ceil(d_model/16)

    # RG-LRU (recurrentgemma)
    lru_width: int = 0              # 0 -> d_model
    conv_width: int = 4

    # modality frontend stub: input_specs() provides precomputed embeddings
    frontend: str = "none"          # none | audio | vision

    # numerics / execution
    dtype: str = "bfloat16"         # activation/compute dtype
    param_dtype: str = "float32"
    remat: str = "full"             # none | full | dots
    attention_impl: str = "reference"  # reference | pallas
    kv_cache_dtype: str = "bfloat16"   # bfloat16 | int8 (quantized cache)
    scan_layers: bool = True
    logits_f32: bool = True
    # cost-measurement mode: unroll inner lax.scans (attention KV blocks,
    # SSM time chunks) into python loops so compiled cost_analysis() FLOPs
    # are exact (XLA does not multiply while-loop bodies by trip count)
    unroll_inner: bool = False
    attn_block_q: int = 1024   # query-block size of the block-causal attention
    scan_chunk: int = 256      # time-chunk of the SSM/RG-LRU chunked scans

    def __post_init__(self):
        for b in self.block_pattern:
            assert b in BLOCK_KINDS, b
        assert self.family in ("dense", "moe", "ssm", "hybrid", "vlm", "audio")
        assert self.router_score in ("softmax", "sigmoid"), self.router_score
        # leading dense layers are of the pattern's one kind
        assert not self.first_dense_layers or self.pattern_period == 1
        assert 0 <= self.expert_offset and self.expert_offset + self.n_held_experts <= self.n_experts

    # -- derived -------------------------------------------------------------------

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // max(self.n_heads, 1)

    @property
    def resolved_dt_rank(self) -> int:
        return self.dt_rank if self.dt_rank else math.ceil(self.d_model / 16)

    @property
    def resolved_lru_width(self) -> int:
        return self.lru_width if self.lru_width else self.d_model

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def n_held_experts(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def pattern_period(self) -> int:
        return len(self.block_pattern)

    @property
    def n_groups(self) -> int:
        return (self.n_layers - self.first_dense_layers) // self.pattern_period

    @property
    def n_rest_layers(self) -> int:
        return (self.n_layers - self.first_dense_layers) % self.pattern_period

    def block_kind(self, layer: int) -> str:
        return self.block_pattern[layer % self.pattern_period]

    @property
    def uses_attention(self) -> bool:
        return any(b.startswith("attn") or b == "mla" for b in self.block_pattern)

    @property
    def pure_global_attention(self) -> bool:
        return all(b == "attn" for b in self.block_pattern)

    def _moe_layer(self, i: int) -> bool:
        return self.is_moe and i >= self.first_dense_layers and i % self.moe_every == 0

    @property
    def n_params(self) -> float:
        """Approximate parameter count held here (used for 6ND model-FLOPs)."""
        d, v = self.d_model, self.vocab_size
        total = v * d * (1 if self.tie_embeddings else 2)
        hd = self.resolved_head_dim
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d
        r, qn, qr, vd = self.kv_lora_rank, self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        mla = d * self.n_heads * (qn + qr) + d * (r + qr) + r * self.n_heads * (qn + vd) + self.n_heads * vd * d
        mlp_mult = 3 if self.mlp_act in ("swiglu", "geglu") else 2
        dense_mlp = mlp_mult * d * self.d_ff
        moe_mlp = (
            self.n_held_experts * mlp_mult * d * self.expert_d_ff
            + mlp_mult * d * self.shared_expert_d_ff
            + d * self.n_experts
        )
        di, ds, dtr = self.d_inner, self.ssm_state, self.resolved_dt_rank
        mamba = 2 * d * di + di * self.ssm_conv + di * (dtr + 2 * ds) + dtr * di + di * ds + di + di * d
        lw = self.resolved_lru_width
        rglru = 2 * d * lw + lw * self.conv_width + 2 * lw * lw + lw + lw * d
        for i in range(self.n_layers):
            kind = self.block_kind(i)
            if kind in ("attn", "attn_local", "mla"):
                total += mla if kind == "mla" else attn
                total += moe_mlp if self._moe_layer(i) else dense_mlp
            elif kind == "mamba":
                total += mamba
            elif kind == "rglru":
                total += rglru
                total += moe_mlp if self._moe_layer(i) else dense_mlp
        return float(total)

    @property
    def n_active_params(self) -> float:
        """Active params per token (MoE: only top_k experts count)."""
        if not self.is_moe:
            return self.n_params
        d = self.d_model
        mlp_mult = 3 if self.mlp_act in ("swiglu", "geglu") else 2
        inactive = max(self.n_held_experts - self.top_k, 0) * mlp_mult * d * self.expert_d_ff
        n_moe_layers = sum(
            1
            for i in range(self.n_layers)
            if self.block_kind(i) != "mamba" and self._moe_layer(i)
        )
        return self.n_params - n_moe_layers * inactive

    def reduced(self, **overrides) -> "ModelConfig":
        """A tiny same-family config for CPU smoke tests."""
        small = dict(
            n_layers=min(self.n_layers, 2 * self.pattern_period),
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 4) if self.n_kv_heads > 1 else 1,
            d_ff=256,
            vocab_size=512,
            head_dim=32,
            window=min(self.window, 64),
            n_experts=min(self.n_experts, 4),
            top_k=min(self.top_k, 2),
            expert_d_ff=64 if self.expert_d_ff else 0,
            shared_expert_d_ff=64 if self.shared_expert_d_ff else 0,
            lru_width=128 if self.lru_width else 0,
            kv_lora_rank=32 if self.kv_lora_rank else 0,
            qk_nope_head_dim=16 if self.qk_nope_head_dim else 0,
            qk_rope_head_dim=8 if self.qk_rope_head_dim else 0,
            v_head_dim=16 if self.v_head_dim else 0,
            experts_held=0,
            expert_offset=0,
            dt_rank=8,
            remat="none",
            name=self.name + "-smoke",
        )
        small.update(overrides)
        return replace(self, **small)
