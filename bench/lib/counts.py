"""Operations and bytes that the algorithm needs, counted from shapes.

These are the numerators of the rooflines and utilisations: they count the
work itself, not what an implementation does (no recomputation under remat,
no extra passes), so they stay the same when a kernel changes.
"""
from __future__ import annotations

import math


def qwen2_params(c: dict) -> dict:
    """Parameter counts of a Qwen2-style decoder from its HF config keys:
    {'embed', 'per_layer', 'total', 'matmul'}. 'matmul' counts the weights
    that enter a matrix product per token: every layer matrix and, tied,
    the embedding once as the output head."""
    d, f, l = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // h
    attn_w = d * h * hd + 2 * d * kv * hd + h * hd * d
    attn_b = h * hd + 2 * kv * hd
    mlp = 3 * d * f
    norms = 2 * d
    per_layer = attn_w + attn_b + mlp + norms
    embed = c["vocab_size"] * d
    total = embed + l * per_layer + d
    matmul = l * (attn_w + mlp) + embed
    return {"embed": embed, "per_layer": per_layer, "total": total,
            "matmul": matmul}


def lm_train_flops_per_token(c: dict, seq: int) -> float:
    """Forward + backward FLOPs per trained token: 6 per matrix weight, plus
    causal attention's two products (QK^T and AV over on average (S+1)/2
    keys: 2 * 2 * H * hd * (S+1)/2 forward per layer, three times that with
    the backward)."""
    d, l = c["hidden_size"], c["num_hidden_layers"]
    attn = 3 * 2 * 2 * d * (seq + 1) / 2 * l
    return 6.0 * qwen2_params(c)["matmul"] + attn


def ssca_update_bytes(n_params: int, param_bytes: int, grad_bytes: int,
                      buf_bytes: int = 4) -> int:
    """Least HBM traffic of one SSCA update (eqs. (9), (10), (5)) over
    ``n_params`` weights: read params, the surrogate buffer g and the
    gradient; write g and params."""
    return n_params * (2 * param_bytes + 2 * buf_bytes + grad_bytes)


def int8_encode_bytes(rows: int, dim: int, chunk: int = 256) -> int:
    """Least HBM traffic of the int8 error-feedback encode of ``rows``
    uploads of ``dim`` float32 each: read the upload and the residual, write
    the int8 payload, one float32 scale per ``chunk`` and the new residual."""
    chunks = math.ceil(dim / chunk)
    return rows * (dim * (4 + 4 + 1 + 4) + chunks * 4)


def mlp_params(features: int, hidden: int, classes: int) -> int:
    """P of the paper's two-layer network without biases."""
    return hidden * features + classes * hidden


def mlp_round_flops(n_params: int, samples: int) -> float:
    """Client forward + backward FLOPs of one round over ``samples`` rows:
    2 per weight forward, 4 backward."""
    return 6.0 * n_params * samples
