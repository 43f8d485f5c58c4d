"""moonlight-16b-a3b [moe] — 27L d_model=2048 16H, multi-head latent
attention (no query compression: kv_lora_rank 512, qk nope 128 + rope 64 a
head, v 128), one leading dense SwiGLU layer of width 11264, then 26 MoE
layers of 64 routed experts (width 1408, top-6 by sigmoid scores with a
selection-only bias, renormalised gates × 2.446) and 2 shared experts (one
SwiGLU of width 2 × 1408); RMSNorm eps 1e-5, rope theta 50000, untied head,
vocab 163840.  The DeepSeek-V3 block at width 2048.
[hf:moonshotai/Moonlight-16B-A3B config.json; arXiv:2502.16982].

Every expert is held here; a deployment that divides the experts over
chips sets ``experts_held``/``expert_offset`` to one chip's share.
"""
from ..models.config import ModelConfig
from . import ArchSpec

ARCH = ArchSpec(
    config=ModelConfig(
        name="moonlight-16b-a3b",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=11264,
        vocab_size=163840,
        block_pattern=("mla",),
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        first_dense_layers=1,
        n_experts=64,
        top_k=6,
        expert_d_ff=1408,
        shared_expert_d_ff=2 * 1408,
        router_score="sigmoid",
        router_bias=True,
        routed_scale=2.446,
        mlp_act="swiglu",
        norm_eps=1e-5,
        rope_theta=50_000.0,
    ),
    microbatches={"train_4k": 16},
)
