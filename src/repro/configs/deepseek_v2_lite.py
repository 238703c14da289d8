"""deepseek-v2-lite — 27L d2048 16H, multi-head latent attention with no
query compression and YaRN rope; layer 0 a dense SwiGLU of 10,944, layers
1-26 64 routed experts of 1,408 (softmax router, greedy top-6, weights not
renormalised) plus 2 shared experts. 15.7 B parameters, 2.4 B active.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json]

``SHARES["ep8"]`` is what one chip holds when each expert layer is divided
over 8 chips (expert parallelism) and attention, router, shared experts
and the dense layer are replicated: 8 of the 64 routed experts of each
layer (ids 0-7) and an eighth of the vocabulary (12,800 rows). Every width
and the router's 64 outputs and 6 choices are as published."""
import dataclasses

from .base import MLAConfig, ModelConfig, MoEConfig, YaRNConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,                 # MLA: latent cache, head count == n_heads
    d_ff=10_944,                   # the leading dense layer's width
    vocab_size=102_400,
    rope_theta=10_000.0,
    norm_eps=1e-6,
    mla=MLAConfig(
        q_lora_rank=None,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_interleaved=True,
        yarn=YaRNConfig(factor=40.0, original_max_position_embeddings=4096,
                        beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                        mscale_all_dim=0.707),
    ),
    moe=MoEConfig(
        n_experts=64,
        top_k=6,
        d_ff_expert=1408,
        norm_topk_prob=False,
        routed_scaling_factor=1.0,
        n_shared_experts=2,
        first_k_dense=1,
    ),
)

SHARES = {
    "ep8": CONFIG.with_(
        name="deepseek-v2-lite@ep8",
        vocab_size=12_800,
        moe=dataclasses.replace(CONFIG.moe, experts_held=8, expert_first=0)),
}


def reduced() -> ModelConfig:
    """Every mechanism at toy width: one dense and two expert layers, 8
    routed experts and a shared one, no query LoRA, YaRN rope."""
    return ModelConfig(
        name="deepseek-v2-lite@smoke",
        family="moe",
        n_layers=3,
        d_model=64,
        n_heads=4,
        n_kv_heads=4,
        d_ff=96,
        vocab_size=128,
        mla=MLAConfig(
            q_lora_rank=None,
            kv_lora_rank=16,
            qk_nope_head_dim=8,
            qk_rope_head_dim=8,
            v_head_dim=8,
            rope_interleaved=True,
            yarn=YaRNConfig(factor=40.0,
                            original_max_position_embeddings=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
        ),
        moe=MoEConfig(
            n_experts=8,
            top_k=3,
            d_ff_expert=32,
            norm_topk_prob=False,
            n_shared_experts=2,
            first_k_dense=1,
        ),
    )
