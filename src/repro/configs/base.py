"""Architecture/config dataclasses shared across the framework.

Every assigned architecture instantiates :class:`ModelConfig` (full size) plus a
reduced smoke variant via :func:`ModelConfig.smoke`; :class:`FLConfig` holds the
paper's federated-learning knobs.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Tuple


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | vlm | audio | mlp
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                 # 0 -> d_model // n_heads
    # --- MoE ---
    n_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0                 # per-expert hidden dim (d_ff used for dense part)
    dense_residual: bool = False      # arctic-style dense MLP in parallel with MoE
    capacity_factor: float = 1.25
    # dropless routing over a held expert range (DeepSeek-V3 style): the
    # layer routes over all n_experts and computes the part of experts
    # [expert_shard * experts_held, +experts_held); 0 = capacity routing
    experts_held: int = 0
    expert_shard: int = 0
    n_shared_experts: int = 0         # always-on experts, width n * moe_d_ff
    first_dense_layers: int = 0       # leading dense-FFN layers before the MoE stack
    router_scoring: str = "softmax"   # softmax | sigmoid
    routed_scaling: float = 1.0       # multiplies the normalized top-k weights
    # --- architecture details ---
    activation: str = "swiglu"        # swiglu | geglu | gelu
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    # --- attention variant ---
    sliding_window: int = 0           # 0 = full/causal attention
    # multi-head latent attention (MLA) when kv_lora_rank > 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    embed_scale: bool = True          # multiply embeddings by sqrt(d_model)
    # --- SSM / hybrid ---
    ssm_state: int = 0
    ssm_heads: int = 0                # number of SSM heads (mamba2/mLSTM)
    ssm_expand: int = 2
    conv_width: int = 4
    chunk_size: int = 256             # chunked linear-attention block size
    block_pattern: Tuple[str, ...] = ()   # per-layer kinds for xlstm ("m","s") /
                                          # zamba2 handled via shared_attn_every
    shared_attn_every: int = 0        # zamba2: shared attn block after every k blocks
    # --- encoder-decoder ---
    encoder_layers: int = 0           # >0 -> enc-dec model (decoder uses n_layers)
    # --- modality frontend stub ---
    frontend: str = "none"            # none | vision | audio
    num_prefix_tokens: int = 0        # patch/frame embeddings provided precomputed
    # --- numerics / sharding policy ---
    dtype: str = "bfloat16"
    remat: bool = True
    train_sharding: str = "fsdp"      # fsdp | tp
    serve_sharding: str = "tp"
    # --- perf knobs (§Perf hillclimbing; defaults = paper-faithful baseline) ---
    attention_impl: str = "dot"       # dot | chunked (online-softmax, flash-style)
    attention_block: int = 512        # K-block size for chunked attention
    seq_shard_activations: bool = False   # Megatron-style sequence parallelism
    moe_sharding: str = "fsdp"        # fsdp | expert2d (expert x ffn-dim 2D)
    norm_impl: str = "ref"            # ref | fused (custom-VJP RMSNorm backward)
    source: str = ""                  # citation

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    def smoke(self, **overrides) -> "ModelConfig":
        """Reduced variant of the same family: 2 layers, d_model<=512, <=4 experts."""
        d = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4)
        kv = max(1, min(self.n_kv_heads, n_heads))
        small = dict(
            name=self.name + "-smoke",
            n_layers=2,
            d_model=d,
            n_heads=n_heads,
            n_kv_heads=kv,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            head_dim=min(self.resolved_head_dim, d // n_heads),
            vocab_size=min(self.vocab_size, 512),
            n_experts=min(self.n_experts, 4),
            experts_per_token=min(self.experts_per_token, 2),
            experts_held=min(self.experts_held, 4),
            expert_shard=0,
            kv_lora_rank=min(self.kv_lora_rank, 64),
            qk_nope_head_dim=min(self.qk_nope_head_dim, 32),
            qk_rope_head_dim=min(self.qk_rope_head_dim, 16),
            v_head_dim=min(self.v_head_dim, 32),
            moe_d_ff=min(self.moe_d_ff, 128) if self.moe_d_ff else 0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_heads=min(self.ssm_heads, 4) if self.ssm_heads else 0,
            chunk_size=32,
            encoder_layers=2 if self.encoder_layers else 0,
            num_prefix_tokens=min(self.num_prefix_tokens, 8),
            block_pattern=self.block_pattern[:2] if self.block_pattern else (),
            shared_attn_every=2 if self.shared_attn_every else 0,
            sliding_window=min(self.sliding_window, 16) if self.sliding_window else 0,
            dtype="float32",
            remat=False,
        )
        small.update(overrides)
        return dataclasses.replace(self, **small)


@dataclass(frozen=True)
class FLConfig:
    """Federated-learning configuration (the paper's knobs)."""
    num_clients: int = 10
    batch_size: int = 100          # B: per-client minibatch (sample-based) / global (feature-based)
    mode: str = "sample"           # sample | feature  (horizontal vs vertical FL)
    # SSCA stepsizes: rho_t = a1 / t**alpha, gamma_t = a2 / t**alpha_g  (eqs. 4/6)
    a1: float = 0.9
    a2: float = 0.5
    alpha_rho: float = 0.1
    alpha_gamma: float = 0.6
    tau: float = 0.2               # strong-convexity constant in (7)/(15)/(19)/(27)
    # regularized (32) / constrained (40) formulations
    l2_lambda: float = 1e-5
    constrained: bool = False
    cost_limit: float = 0.13       # U in (40)
    penalty_c: float = 1e5         # c in Problem 4/9
