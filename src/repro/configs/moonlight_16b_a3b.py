"""moonlight-16b-a3b [moe]: DeepSeek-V3 block (model_type deepseek_v3) —
multi-head latent attention (kv_lora_rank 512, no q_lora, 128 + 64 RoPE
q·k dims, 128 v dims), one leading dense SwiGLU layer (11 264), then 26
MoE layers of 64 routed experts (width 1408, top-6 by sigmoid score plus
a fixed correction bias, normalized weights x 2.446) and 2 shared experts;
RMSNorm eps 1e-5, untied head, no embedding scale, vocabulary 163 840
[hf:moonshotai/Moonlight-16B-A3B config.json].

Parameters and the optimizer state are float32; products take JAX's
default matmul precision (one bfloat16 pass on the TPU under
``jax_default_matmul_precision="bfloat16"``), accumulating in float32.
``experts_held = n_experts`` is the whole layer; a chip of an
expert-parallel group holds ``experts_held`` of them (``expert_shard``)."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=11264,
    vocab_size=163_840,
    n_experts=64, experts_per_token=6, moe_d_ff=1408,
    experts_held=64, n_shared_experts=2, first_dense_layers=1,
    router_scoring="sigmoid", routed_scaling=2.446,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rope_theta=50_000.0, norm_eps=1e-5,
    tie_embeddings=False, embed_scale=False,
    dtype="float32", attention_impl="chunked",
    source="hf:moonshotai/Moonlight-16B-A3B",
)
