"""deepseek-v2-lite [moe] — 27L d_model=2048 16H MLA vocab=102400.

Multi-head latent attention with no query compression: a 512-wide
latent (``kv_lora_rank``) and a 64-wide rotary key per token are all
the cache holds; per head, keys are 128 + 64 wide and values 128. YaRN
rotary positions (factor 40 over 4096). The first layer is dense (MLP
10944); the other 26 hold 64 routed experts of width 1408, top-6 by an
unnormalized softmax, and 2 shared experts. Untied head.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        n_layers=27,
        n_dense_layers=1,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=10944,
        vocab_size=102400,
        act="swiglu",
        rope_theta=10000.0,
        norm_eps=1e-6,
        moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                      norm_topk=False),
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128, rope_factor=40.0,
                      rope_original_max=4096, beta_fast=32.0,
                      beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
        param_dtype="bfloat16",
    )


def tiny() -> ModelConfig:
    return config().replace(
        name="deepseek-v2-lite-tiny", n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=64, vocab_size=256,
        moe=MoEConfig(n_experts=8, top_k=3, d_expert=32, n_shared=2,
                      norm_topk=False),
        mla=MLAConfig(kv_lora_rank=64, qk_nope_head_dim=16,
                      qk_rope_head_dim=64, v_head_dim=16, rope_factor=40.0,
                      rope_original_max=64, beta_fast=32.0, beta_slow=1.0,
                      mscale=0.707, mscale_all_dim=0.707, q_block=16),
        param_dtype="float32",
    )
