"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rmsnorm_ref(x, scale, eps: float = 1e-6):
    """x: (..., D); scale: (D,). Gemma-style (1+scale) RMSNorm, fp32 internals."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def ssca_update_ref(w, buf, grad, rho, gamma, tau, lam):
    """The fused Algorithm-1-example update chain (eqs. (9)+(10)+(5), λ folded):

        buf' = (1-ρ)·buf + ρ·(grad + (2λ-2τ)·w)
        ω̄   = -buf'/(2τ)
        w'   = (1-γ)·w + γ·ω̄

    All accumulation in fp32; w' cast back to w.dtype.
    """
    w32 = w.astype(jnp.float32)
    buf32 = buf.astype(jnp.float32)
    g32 = grad.astype(jnp.float32)
    new_buf = (1.0 - rho) * buf32 + rho * (g32 + (2.0 * lam - 2.0 * tau) * w32)
    wbar = -new_buf / (2.0 * tau)
    new_w = (1.0 - gamma) * w32 + gamma * wbar
    return new_w.astype(w.dtype), new_buf


def stochastic_quantize_ref(x, bits, qmax: int, chunk: int = 256):
    """Oracle for the fused quantize-dequantize kernel: per-chunk absmax
    scales + stochastic rounding from raw uint32 bits. Delegates to the same
    comm/codecs.py math the codec ref path uses, so codec == kernel exactly.

    x: (P,); bits: uint32, (ceil(P/chunk)·chunk,).
    Returns (values int8 (C·chunk,), scales fp32 (C,), xhat fp32 (P,)).
    """
    from repro.comm.codecs import (chunk_pad, stochastic_round_chunks,
                                   uniform_from_bits)
    p = x.shape[0]
    xc = chunk_pad(x, chunk)
    u = uniform_from_bits(bits.reshape(xc.shape))
    q, scales = stochastic_round_chunks(xc, u, qmax)
    xhat = (q.astype(jnp.float32) * scales[:, None]).reshape(-1)[:p]
    return q.reshape(-1), scales, xhat


def flash_attention_ref(q, k, v, *, causal=True, window=0, scale=None):
    """q, k: (B,H|KV,Sq|Sk,D); v: (B,KV,Sk,Dv); GQA via H % KV == 0. fp32
    softmax. A row that sees no key reads NaN."""
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    rep = h // kvh
    qg = q.reshape(b, kvh, rep, sq, d)
    logits = jnp.einsum("bkrqd,bksd->bkrqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = jnp.arange(sq, dtype=jnp.int32)[:, None]
    kpos = jnp.arange(sk, dtype=jnp.int32)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos + (sk - sq)        # right-aligned when sq < sk
    if window:
        mask &= (qpos + (sk - sq)) - kpos < window
    logits = jnp.where(mask, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkrqs,bksd->bkrqd", w, v.astype(jnp.float32))
    return out.reshape(b, h, sq, v.shape[-1]).astype(q.dtype)
