"""sdar-30b-a3b [moe] — SDAR-30B-A3B-Chat, a block-diffusion LM on the
Qwen3-MoE decoder layer (huggingface.co/JetLM/SDAR-30B-A3B-Chat
config.json).  48L d_model=2048, GQA 32 query / 4 KV heads at head_dim
128 with per-head q/k RMSNorm, RoPE theta 1e6, 128 experts of width 768
top-8 (gates renormalised) in every layer, no shared expert,
vocab=151936, untied head, no time input.

Its attention is block-causal: bidirectional inside a block, causal
across blocks.  config.json gives no block length; 4 is assumed (the
block length of SDAR's released chat usage).
"""
from repro.models.config import ModelConfig, moe_pattern


def get_config() -> ModelConfig:
    return ModelConfig(
        name="sdar-30b-a3b", arch_type="moe",
        n_layers=48, d_model=2048, n_heads=32, n_kv_heads=4, head_dim=128,
        d_ff=768, vocab_size=151936,
        block_pattern=moe_pattern(48),
        n_experts=128, experts_per_token=8,
        qk_norm=True, block_causal=4, rope_theta=1e6, norm_eps=1e-6,
        time_conditioning=False, bidirectional=True, dtype="bfloat16",
        paper="huggingface.co/JetLM/SDAR-30B-A3B-Chat",
    )
