"""moonlight-16b-a3b [mla+moe]: 27L d2048 16H MLA (kv_lora 512, nope 128 +
rope 64, v 128; no q low-rank), first layer dense SwiGLU 11264, then 64
routed experts of 1408 top-6 by sigmoid + selection bias (renormalised,
x 2.446) beside 2 shared, vocab 163840 untied, rms eps 1e-5, rope theta
50000. [hf:moonshotai/Moonlight-16B-A3B config.json; DeepSeek-V3 block,
arXiv:2412.19437]

`experts_held` / `expert_offset` give one chip's share under expert
parallelism (0 = all 64 here)."""
from repro.models.transformer import TransformerConfig

INPUT_KIND = "tokens"


def config() -> TransformerConfig:
    return TransformerConfig(
        name="moonlight-16b-a3b", n_layers=27, d_model=2048, n_heads=16,
        n_kv_heads=16, d_ff=11264, vocab_size=163840, rope_base=50000.0,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, first_dense_layers=1, num_experts=64, top_k=6,
        moe_d_ff=1408, n_shared_experts=2, routed_scaling=2.446,
        rms_norm_eps=1e-5, tie_embeddings=False,
        param_dtype="bfloat16")


def reduced() -> TransformerConfig:
    return TransformerConfig(
        name="moonlight-16b-a3b-smoke", n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=96, vocab_size=256, rope_base=50000.0,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, first_dense_layers=1, num_experts=16, top_k=4,
        moe_d_ff=24, n_shared_experts=2, routed_scaling=2.446,
        rms_norm_eps=1e-5, tie_embeddings=False)
