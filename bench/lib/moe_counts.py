"""Parameters and operations of a DeepSeek-V3-block model (Moonlight-16B-A3B)
counted from its configuration's shapes (HF config keys, as in
``bench/configs/moonlight-16b-a3b.json``): latent attention, leading dense
layers, and MoE layers holding ``n_routed_experts`` of ``router_experts``
with shared experts. Like ``counts.py`` these count the work itself: no
recomputation under remat, and a causal attention's half of the keys.
"""
from __future__ import annotations


def _attn(c: dict) -> int:
    d, h, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    nope, rd, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                    c["v_head_dim"])
    return (d * h * (nope + rd) + d * (r + rd) + r * h * (nope + vd)
            + h * vd * d)


def _expert(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def moonlight_params(c: dict) -> dict:
    """Parameter counts: {'attn', 'dense_layer', 'moe_layer', 'embed',
    'total'} (attention per layer includes the latent norm; each layer
    adds its two norms; 'embed' is the embedding, the untied head and the
    final norm)."""
    d = c["hidden_size"]
    attn = _attn(c) + c["kv_lora_rank"]
    dense_layer = attn + 2 * d + 3 * d * c["intermediate_size"]
    moe_layer = (attn + 2 * d + d * c["router_experts"]
                 + (c["n_routed_experts"] + c["n_shared_experts"]) * _expert(c))
    embed = 2 * c["vocab_size"] * d + d
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    return {"attn": attn, "dense_layer": dense_layer, "moe_layer": moe_layer,
            "embed": embed,
            "total": n_dense * dense_layer + n_moe * moe_layer + embed}


def moonlight_matmul_per_token(c: dict) -> float:
    """Weights that enter a matrix product per token, the routed experts by
    their expected share here: top-k x held / router experts of them."""
    d = c["hidden_size"]
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    routed = (c["num_experts_per_tok"] * c["n_routed_experts"]
              / c["router_experts"])
    moe = (d * c["router_experts"]
           + (routed + c["n_shared_experts"]) * _expert(c))
    return (c["num_hidden_layers"] * _attn(c)
            + n_dense * 3 * d * c["intermediate_size"] + n_moe * moe
            + c["vocab_size"] * d)


def moonlight_train_flops_per_token(c: dict, seq: int) -> float:
    """Forward + backward FLOPs per trained token: 6 per matrix weight, plus
    causal attention's QK^T (q.k head dim) and AV (v head dim) over on
    average (seq + 1) / 2 keys, three times over with the backward."""
    h = c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    attn = 3 * 2 * h * (qk + c["v_head_dim"]) * (seq + 1) / 2
    return (6.0 * moonlight_matmul_per_token(c)
            + c["num_hidden_layers"] * attn)


def expert_gmm_flops(slots: float, c: dict) -> float:
    """FLOPs of the held experts' grouped products over ``slots``
    token-slots: three products of 2 d f each, forward and the backward's
    two (x3)."""
    return slots * 3 * 2 * c["hidden_size"] * c["moe_intermediate_size"] * 3
