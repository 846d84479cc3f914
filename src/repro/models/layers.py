"""Shared neural-net layers for the model zoo (pure-function style, dict pytrees).

Conventions:
  - activations:  (B, S, D) ; attention heads laid out (B, S, H, Hd)
  - stacked layer params carry a leading L axis and are consumed by lax.scan
  - params are created in ``param_dtype`` and computation runs in ``dtype``
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.obs.trace import phase

# ---------------------------------------------------------------------------
# ambient-mesh activation sharding
# ---------------------------------------------------------------------------


def _ambient_mesh():
    """The mesh set by `jax.set_mesh` around the call, or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _ambient_axes():
    """Axis names of the mesh in context (`jax.set_mesh`), or ()."""
    m = _ambient_mesh()
    return tuple(m.axis_names) if m is not None else ()


def model_axis_divides(n: int) -> bool:
    """True iff the ambient mesh has a 'model' axis whose size divides n."""
    m = _ambient_mesh()
    if m is None or "model" not in m.axis_names:
        return False
    return n % m.shape["model"] == 0


def shard_spec(x, entries):
    """with_sharding_constraint with raw entries; no-op outside a mesh."""
    axes = _ambient_axes()
    if not axes:
        return x
    fixed = []
    for e in entries:
        if e == "batch":
            fixed.append(tuple(a for a in ("pod", "data") if a in axes) or None)
        elif e is None or e in axes:
            fixed.append(e)
        else:
            fixed.append(None)
    while len(fixed) < x.ndim:
        fixed.append(None)
    try:
        return jax.lax.with_sharding_constraint(x, P(*fixed))
    except (RuntimeError, ValueError):
        return x


def shard_batch(x, *rest):
    """Constrain activation sharding: dim0 = batch over the data axes of the
    ambient mesh ('pod','data'), remaining dims per `rest` entries (axis names
    filtered against the mesh). No-op outside a mesh context (smoke tests).

    This is not just a perf knob: batch-sharding the activations IS the
    paper's client partitioning (clients = data shards) — XLA must never
    gather per-client activations to a single shard.
    """
    axes = _ambient_axes()
    if not axes:
        return x
    batch_axes = tuple(a for a in ("pod", "data") if a in axes)
    if not batch_axes:
        return x
    entries = [batch_axes]
    for r in rest:
        entries.append(r if (r is None or r in axes) else None)
    while len(entries) < x.ndim:
        entries.append(None)
    try:
        return jax.lax.with_sharding_constraint(x, P(*entries))
    except (RuntimeError, ValueError):
        return x


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, fan_in: Optional[int] = None):
    fan_in = fan_in if fan_in is not None else shape[-2] if len(shape) >= 2 else shape[-1]
    scale = 1.0 / math.sqrt(max(1, fan_in))
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm_init(d, dtype):
    return {"scale": jnp.zeros((d,), dtype)}   # gemma-style (1 + scale)


def rmsnorm(params, x, eps: float):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(dt)


# --- fused-backward variant (§Perf): identical math, but the VJP keeps all
# (B,S,D)-sized tensors in the input dtype — only per-row statistics are fp32.
# The autodiff of the reference materializes several fp32 residual-stream
# tensors per norm per direction (measured: the dominant memory-term item on
# deepseek-67b/qwen train; EXPERIMENTS.md §Perf).


import functools as _ft


@_ft.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_fused(x, scale, eps):
    return rmsnorm({"scale": scale}, x, eps)


def _rms_fused_fwd(x, scale, eps):
    x32 = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    y = (x32 * r * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)
    return y, (x, scale, r)


def _rms_fused_bwd(eps, res, dy):
    x, scale, r = res
    d = x.shape[-1]
    g1 = (1.0 + scale.astype(jnp.float32)).astype(x.dtype)
    rd = r.astype(x.dtype)                                  # (.., 1) broadcast
    t = x * (dy * g1)                                        # elementwise, x.dtype
    s1 = jnp.sum(t.astype(jnp.float32), axis=-1, keepdims=True)   # fp32 rows
    dx = (dy * g1) * rd - x * ((r ** 3) * (s1 / d)).astype(x.dtype)
    dscale = jnp.sum((x * dy).astype(jnp.float32) * r,
                     axis=tuple(range(x.ndim - 1)))
    return dx.astype(x.dtype), dscale.astype(scale.dtype)


_rms_fused.defvjp(_rms_fused_fwd, _rms_fused_bwd)


def norm(params, x, cfg):
    """RMSNorm dispatcher: cfg.norm_impl selects ref vs fused-backward."""
    if getattr(cfg, "norm_impl", "ref") == "fused":
        return _rms_fused(x, params["scale"], cfg.norm_eps)
    return rmsnorm(params, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float):
    """x: (..., S, H, Hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq          # (..., S, half)
    ang = ang[..., None, :]                                        # (..., S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA / MQA, causal, sliding-window, prefix-LM, cross, decode)
# ---------------------------------------------------------------------------


def attn_init(key, cfg, dtype, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dtype, fan_in=d),
        "wk": dense_init(ks[1], (d, kv * hd), dtype, fan_in=d),
        "wv": dense_init(ks[2], (d, kv * hd), dtype, fan_in=d),
        "wo": dense_init(ks[3], (h * hd, d), dtype, fan_in=h * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    return p


def _qkv(params, x, cfg):
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q = q + params["bq"].astype(q.dtype)
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    b, s, _ = x.shape
    return (q.reshape(b, s, h, hd), k.reshape(b, s, kv, hd), v.reshape(b, s, kv, hd))


def make_attention_mask(q_pos, k_pos, *, causal=True, window=0, prefix_len=0):
    """(..., Sq, Sk) boolean mask. prefix positions attend bidirectionally."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    if causal:
        m = kp <= qp
    else:
        m = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if window:
        m = m & (qp - kp < window)
    if prefix_len:
        m = m | (kp < prefix_len)
    return m


def dot_attention(q, k, v, mask, *, kv_heads_repeat: int):
    """q:(B,Sq,H,Hd) k,v:(B,Sk,KV,Hd) mask:(B|1,Sq,Sk) -> (B,Sq,H,Hd).

    GQA is handled by broadcasting K/V to H heads (a local view — KV is
    replicated or head-sharded consistently, so no collective is induced).
    Sharding: heads over the 'model' axis when H divides it; otherwise the
    query-sequence dim is model-sharded (sequence-parallel attention; softmax
    is over the K dim, which stays local either way).
    """
    b, sq, h, hd = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    if kv_heads_repeat > 1:
        k = jnp.broadcast_to(k[:, :, :, None, :],
                             (b, sk, kvh, kv_heads_repeat, hd)).reshape(b, sk, h, hd)
        v = jnp.broadcast_to(v[:, :, :, None, :],
                             (b, sk, kvh, kv_heads_repeat, hd)).reshape(b, sk, h, hd)
    hdiv = model_axis_divides(h)
    q = shard_spec(q, ["batch", None, "model", None] if hdiv
                   else ["batch", "model", None, None])
    logits = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32)
    logits = logits / math.sqrt(hd)
    logits = shard_spec(logits, ["batch", "model", None, None] if hdiv
                        else ["batch", None, "model", None])
    logits = jnp.where(mask[:, None, :, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", w, v)
    return shard_spec(out, ["batch", None, "model", None] if hdiv
                      else ["batch", "model", None, None])


def _cattn_mask(qp, kpp, causal, window, prefix_len, sq, blk):
    ok = jnp.ones((1, sq, blk), bool)
    if causal:
        ok &= kpp <= qp
    if window:
        ok &= qp - kpp < window
    if prefix_len:
        ok |= kpp < prefix_len
    ok &= kpp < 2**30                                           # padding
    return ok


def _cattn_fwd_scan(qt, kb, vb, kp, qp, scale, causal, window, prefix_len):
    b, h, sq, hd = qt.shape
    blk = kb.shape[3]

    def step(carry, inp):
        m, l, acc = carry
        kblk, vblk, kpb = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, kblk) * scale
        ok = _cattn_mask(qp, kpb[:, None, :], causal, window, prefix_len, sq, blk)
        s = jnp.where(ok[:, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(ok[:, None], jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vblk)
        return (m_new, l, acc), None

    m0 = jnp.full((b, h, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, vb.shape[-1]), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, kp))
    lse = m + jnp.log(jnp.where(l == 0.0, 1.0, l))              # logsumexp rows
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out, lse


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _cattn(qt, kb, vb, kp, qp, causal, window, prefix_len):
    """Flash-attention forward (blocked online softmax). The custom VJP
    recomputes p blockwise in the backward pass (standard flash backward) —
    nothing O(Sq·Sk) is ever saved, unlike grad-of-scan which stashes every
    per-block tensor (measured +63% HBM traffic on deepseek-67b; §Perf log).

    qt: (B,H,Sq,Hd) f32; kb,vb: (N,B,H,blk,Hd) f32; kp: (N,1,blk); qp: (1,Sq,1).
    """
    hd = qt.shape[-1]
    out, _ = _cattn_fwd_scan(qt, kb, vb, kp, qp, 1.0 / math.sqrt(hd),
                             causal, window, prefix_len)
    return out


def _cattn_fwd(qt, kb, vb, kp, qp, causal, window, prefix_len):
    hd = qt.shape[-1]
    out, lse = _cattn_fwd_scan(qt, kb, vb, kp, qp, 1.0 / math.sqrt(hd),
                               causal, window, prefix_len)
    return out, (qt, kb, vb, kp, qp, out, lse)


def _cattn_bwd(causal, window, prefix_len, res, dout):
    qt, kb, vb, kp, qp, out, lse = res
    b, h, sq, hd = qt.shape
    blk = kb.shape[3]
    scale = 1.0 / math.sqrt(hd)
    delta = jnp.sum(dout * out, axis=-1)                        # (B,H,Sq)

    def step(dq, inp):
        kblk, vblk, kpb = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, kblk) * scale
        ok = _cattn_mask(qp, kpb[:, None, :], causal, window, prefix_len, sq, blk)
        p = jnp.where(ok[:, None], jnp.exp(s - lse[..., None]), 0.0)
        dv = jnp.einsum("bhqk,bhqd->bhkd", p, dout)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dout, vblk)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kblk)
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qt)
        return dq, (dk, dv)

    dq0 = jnp.zeros_like(qt)
    dq, (dk, dv) = jax.lax.scan(step, dq0, (kb, vb, kp))
    return dq, dk, dv, None, None


_cattn.defvjp(_cattn_fwd, _cattn_bwd)


def chunked_attention(q, k, v, q_pos, k_pos, *, causal=True, window=0,
                      prefix_len=0, block=512):
    """Flash attention: q,k: (B,Sq|Sk,H,Hd); v: (B,Sk,H,Hv) (already
    GQA-broadcast; Hv may differ from Hd, as in latent attention);
    q_pos/k_pos: (1, Sq)/(1, Sk). Returns (B,Sq,H,Hv).

    Where the program is compiled for a TPU, causal self-attention (the
    same positions 0..S-1 for queries and keys, no window, no prefix)
    outside a mesh, over a sequence that whole 128-row tiles cover, runs
    the Pallas kernel of ``kernels/flash_attention.py``, which skips the
    tiles the mask hides. Everywhere else, and as the tests' reference, the
    blocked online softmax of ``_chunked_scan`` runs.
    """
    sq, sk = q.shape[1], k.shape[1]
    scan = _functools.partial(_chunked_scan, causal=causal, window=window,
                              prefix_len=prefix_len, block=block)
    if (causal and not window and not prefix_len and sq == sk
            and sq % 128 == 0 and _ambient_mesh() is None):
        return jax.lax.platform_dependent(q, k, v, q_pos, k_pos,
                                          tpu=_flash_kernel, default=scan)
    return scan(q, k, v, q_pos, k_pos)


def _flash_kernel(q, k, v, q_pos, k_pos):
    """The causal Pallas kernel on (B,S,H,D) inputs; the products take
    the operands XLA's would under the configured matmul precision (one
    bfloat16 pass for float32 inputs at the default). A Mosaic kernel is
    not partitioned by XLA, hence no mesh. Imported here: Pallas takes
    1-2 s to import, which a program without attention need not pay."""
    from repro.kernels.flash_attention import flash_attention_pallas
    del q_pos, k_pos                    # 0..S-1 for both: masked by index
    single_pass = jax.config.jax_default_matmul_precision in (
        None, "default", "fastest", "bfloat16", "BF16_BF16_F32")
    mxu = jnp.bfloat16 if q.dtype == jnp.float32 and single_pass else None
    t = lambda x: x.transpose(0, 2, 1, 3)                       # (B,H,S,D)
    return t(flash_attention_pallas(t(q), t(k), t(v), causal=True,
                                    mxu_dtype=mxu))


def _chunked_scan(q, k, v, q_pos, k_pos, *, causal, window, prefix_len,
                  block):
    """Flash-attention algorithm at the XLA level: blocked online softmax
    with a recompute-based custom VJP. Never materializes the (Sq, Sk)
    logits; the working set is (Sq, block)."""
    b, sq, h, hd = q.shape
    sk, hv = k.shape[1], v.shape[-1]
    blk = min(block, sk)
    pad = (-sk) % blk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=2**30)
    n = k.shape[1] // blk
    hdiv = model_axis_divides(h)
    qspec = ["batch", None, "model", None] if hdiv else ["batch", "model", None, None]
    q = shard_spec(q, qspec)
    qt = q.transpose(0, 2, 1, 3).astype(jnp.float32)            # (B,H,Sq,Hd)
    kb = k.reshape(b, n, blk, h, hd).transpose(1, 0, 3, 2, 4).astype(jnp.float32)
    vb = v.reshape(b, n, blk, h, hv).transpose(1, 0, 3, 2, 4).astype(jnp.float32)
    kp = k_pos.reshape(1, n, blk).transpose(1, 0, 2)            # (N,1,blk)
    qp = q_pos[..., :, None]                                    # (1,Sq,1)
    out = _cattn(qt, kb, vb, kp, qp, causal, window, prefix_len)
    out = out.transpose(0, 2, 1, 3).astype(v.dtype)
    return shard_spec(out, qspec)


def attention(params, x, positions, cfg, *, mask=None, kv_override=None):
    """Full (training/prefill) attention. kv_override: (k, v) for cross-attention."""
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q, k, v = _qkv(params, x, cfg)
    if kv_override is not None:
        k, v = kv_override
    else:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    b, s = x.shape[:2]
    if getattr(cfg, "attention_impl", "dot") == "chunked" and kv_override is None:
        rep = h // k.shape[2]
        if rep > 1:
            sk, kvh = k.shape[1], k.shape[2]
            k = jnp.broadcast_to(k[:, :, :, None, :],
                                 (b, sk, kvh, rep, hd)).reshape(b, sk, h, hd)
            v = jnp.broadcast_to(v[:, :, :, None, :],
                                 (b, sk, kvh, rep, hd)).reshape(b, sk, h, hd)
        out = chunked_attention(q, k, v, positions, positions, causal=True,
                                window=cfg.sliding_window,
                                prefix_len=getattr(cfg, "_prefix_len", 0),
                                block=cfg.attention_block)
    else:
        if mask is None:
            mask = make_attention_mask(positions, positions, causal=True,
                                       window=cfg.sliding_window)
        out = dot_attention(q, k, v, mask, kv_heads_repeat=h // k.shape[2])
    return out.reshape(b, s, h * hd) @ params["wo"]


def attention_decode(params, x, cache_k, cache_v, pos, cfg, *, window=0):
    """One-token decode against a preallocated KV cache.

    x: (B, 1, D); cache_k/v: (B, S_max, KV, Hd); pos: scalar int32 (current index).
    Returns (out, new_cache_k, new_cache_v).
    """
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b = x.shape[0]
    q, k, v = _qkv(params, x, cfg)
    p1 = jnp.full((b, 1), pos, jnp.int32)
    q = rope(q, p1, cfg.rope_theta)
    k = rope(k, p1, cfg.rope_theta)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype), (0, pos, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype), (0, pos, 0, 0))
    s_max = cache_k.shape[1]
    k_pos = jnp.arange(s_max, dtype=jnp.int32)[None, :]
    mask = k_pos <= pos
    if window:
        mask = mask & (pos - k_pos < window)
    mask = mask[:, None, :]                      # (1, 1, S_max), broadcast
    out = dot_attention(q, cache_k.astype(q.dtype), cache_v.astype(q.dtype), mask,
                        kv_heads_repeat=h // kv)
    out = out.reshape(b, 1, h * hd) @ params["wo"]
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2/V3 MLA, no query compression)
# ---------------------------------------------------------------------------


def mla_init(key, cfg, dtype):
    """q = x Wq (H heads of nope + rope dims); [c_kv, k_pe] = x Wkv_a;
    [k_nope, v] = RMSNorm(c_kv) Wkv_b per head; out = concat_h(o_h) Wo."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, h * (nope + rd)), dtype, fan_in=d),
        "wkv_a": dense_init(ks[1], (d, r + rd), dtype, fan_in=d),
        "kv_norm": rmsnorm_init(r, dtype),
        "wkv_b": dense_init(ks[2], (r, h * (nope + vd)), dtype, fan_in=r),
        "wo": dense_init(ks[3], (h * vd, d), dtype, fan_in=h * vd),
    }


def _mla_q(params, x, positions, cfg):
    b, s, _ = x.shape
    nope = cfg.qk_nope_head_dim
    q = (x @ params["wq"]).reshape(b, s, cfg.n_heads, -1)
    return jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], positions, cfg.rope_theta)], -1)


def mla_latent(params, x, positions, cfg):
    """What MLA caches per token: the normed latent c_kv (B, S, r) and the
    RoPE'd key part k_pe (B, S, rope), which every head shares."""
    r = cfg.kv_lora_rank
    kva = x @ params["wkv_a"]
    c_kv = rmsnorm(params["kv_norm"], kva[..., :r], cfg.norm_eps)
    k_pe = rope(kva[..., None, r:], positions, cfg.rope_theta)[..., 0, :]
    return c_kv, k_pe


def _mla_kv(params, c_kv, k_pe, cfg):
    b, s, _ = c_kv.shape
    h, nope = cfg.n_heads, cfg.qk_nope_head_dim
    kv = (c_kv @ params["wkv_b"]).reshape(b, s, h, -1)
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :], (b, s, h, k_pe.shape[-1]))
    return jnp.concatenate([kv[..., :nope], k_pe], -1), kv[..., nope:]


def mla_attention(params, x, positions, cfg):
    """Causal MLA over a full sequence; scores scale 1/sqrt(nope + rope).
    With ``attention_impl="chunked"`` no (H, S, S) array is ever held."""
    b, s, _ = x.shape
    with phase("mla-attention"):
        q = _mla_q(params, x, positions, cfg)
        k, v = _mla_kv(params, *mla_latent(params, x, positions, cfg), cfg)
        if cfg.attention_impl == "chunked":
            out = chunked_attention(q, k, v, positions, positions, causal=True,
                                    block=cfg.attention_block)
        else:
            mask = make_attention_mask(positions, positions, causal=True)
            out = dot_attention(q, k, v, mask, kv_heads_repeat=1)
        return out.reshape(b, s, -1) @ params["wo"]


def mla_decode(params, x, cache_c, cache_pe, pos, cfg):
    """One-token MLA decode against the latent cache: cache_c (B, S_max, r),
    cache_pe (B, S_max, rope). Returns (out, cache_c, cache_pe)."""
    b = x.shape[0]
    p1 = jnp.full((b, 1), pos, jnp.int32)
    q = _mla_q(params, x, p1, cfg)
    c_kv, k_pe = mla_latent(params, x, p1, cfg)
    cache_c = jax.lax.dynamic_update_slice(
        cache_c, c_kv.astype(cache_c.dtype), (0, pos, 0))
    cache_pe = jax.lax.dynamic_update_slice(
        cache_pe, k_pe.astype(cache_pe.dtype), (0, pos, 0))
    k, v = _mla_kv(params, cache_c.astype(q.dtype), cache_pe.astype(q.dtype),
                   cfg)
    mask = (jnp.arange(cache_c.shape[1]) <= pos)[None, None, :]
    out = dot_attention(q, k, v, mask, kv_heads_repeat=1)
    return out.reshape(b, 1, -1) @ params["wo"], cache_c, cache_pe


# ---------------------------------------------------------------------------
# MLP blocks
# ---------------------------------------------------------------------------


def mlp_init(key, d, d_ff, activation, dtype):
    ks = jax.random.split(key, 3)
    if activation in ("swiglu", "geglu"):
        return {
            "wi": dense_init(ks[0], (d, d_ff), dtype, fan_in=d),
            "wg": dense_init(ks[1], (d, d_ff), dtype, fan_in=d),
            "wo": dense_init(ks[2], (d_ff, d), dtype, fan_in=d_ff),
        }
    return {
        "wi": dense_init(ks[0], (d, d_ff), dtype, fan_in=d),
        "wo": dense_init(ks[2], (d_ff, d), dtype, fan_in=d_ff),
    }


def mlp(params, x, activation: str):
    if activation == "swiglu":
        return (jax.nn.silu(x @ params["wg"]) * (x @ params["wi"])) @ params["wo"]
    if activation == "geglu":
        return (jax.nn.gelu(x @ params["wg"], approximate=True) * (x @ params["wi"])) @ params["wo"]
    return jax.nn.gelu(x @ params["wi"], approximate=True) @ params["wo"]


# ---------------------------------------------------------------------------
# Mixture of Experts (top-k router, scatter dispatch, expert-parallel friendly)
# ---------------------------------------------------------------------------


def moe_init(key, cfg, dtype):
    """Router over all n_experts; expert weights for the experts this layer
    holds (``experts_held``, else all); shared experts as one SwiGLU of
    width n_shared_experts * moe_d_ff."""
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    held = cfg.experts_held or e
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], (d, e), dtype, fan_in=d),
        "wi": dense_init(ks[1], (held, d, ff), dtype, fan_in=d),
        "wg": dense_init(ks[2], (held, d, ff), dtype, fan_in=d),
        "wo": dense_init(ks[3], (held, ff, d), dtype, fan_in=ff),
    }
    if cfg.dense_residual:
        p["dense"] = mlp_init(jax.random.fold_in(key, 7), d, cfg.d_ff, "swiglu", dtype)
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(jax.random.fold_in(key, 11), d,
                               cfg.n_shared_experts * ff, "swiglu", dtype)
    return p


def _one_at_a_time(fn):
    """fn, batched by a loop over the batch axis: `jax.lax.ragged_dot`
    batches only where its weights are batched too, and a vmapped client
    shares them."""
    f = jax.custom_batching.custom_vmap(fn)

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        def one(batched):
            it = iter(batched)
            return fn(*[next(it) if b else a
                        for a, b in zip(args, in_batched)])
        out = jax.lax.map(one, [a for a, b in zip(args, in_batched) if b])
        return out, jax.tree.map(lambda _: True, out)

    return f


def _grouped_rows(y, sizes):
    """y with its rows past sum(sizes) set to 0: the TPU's grouped-product
    kernel leaves those rows unwritten."""
    rows = jnp.arange(y.shape[0])[:, None] < jnp.sum(sizes)
    return jnp.where(rows, y, jnp.zeros((), y.dtype))


def _ragged(x, w, sizes):
    return _grouped_rows(jax.lax.ragged_dot(
        x, w, sizes, preferred_element_type=jnp.float32), sizes)


def _ragged_grads(x, w, sizes, g):
    """(dx, dw) of the grouped product: its products take bfloat16 operands
    too (the cotangent rounded), each accumulating in float32 before its
    bfloat16 result."""
    dx, dw = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes), x, w)[1](
        g.astype(x.dtype))
    return _grouped_rows(dx, sizes), dw


_ragged_fwd = _one_at_a_time(_ragged)
_ragged_bwd = _one_at_a_time(_ragged_grads)


@jax.custom_vjp
def _ragged_dot(x, w, sizes):
    return _ragged_fwd(x, w, sizes)


def _ragged_dot_fwd(x, w, sizes):
    return _ragged_fwd(x, w, sizes), (x, w, sizes)


def _ragged_dot_bwd(res, g):
    x, w, sizes = res
    dx, dw = _ragged_bwd(x, w, sizes, g)
    return dx, dw, None


_ragged_dot.defvjp(_ragged_dot_fwd, _ragged_dot_bwd)


def _gmm(x, w, sizes):
    """Grouped product of expert-sorted rows with their experts' weights:
    one bfloat16 pass, float32 accumulation. Rows past sum(sizes) come out
    0, and so do their gradients."""
    return _ragged_dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), sizes)


def moe_held(params, x, cfg):
    """Dropless MoE over the held expert range (DeepSeek-V3 routing).

    Every token is routed over all ``n_experts``: s = sigmoid(x W_r) (the
    gate in float32, as published), the top ``experts_per_token`` of s + b
    (b, the correction bias, is a fixed buffer; zero here, so the pick is
    the top-k of s), weights s[top] / sum s[top] * routed_scaling. This
    layer computes the part of experts [expert_shard * experts_held,
    +experts_held): the token-slots routed to them are sorted by expert and
    run through grouped products; slots of absent experts add nothing (an
    expert-parallel peer computes them). No capacity, no dropped slot, no
    auxiliary loss. Shared experts run on every token.

    x: (B, S, D). Returns (out, stats): per-layer counts of the held slots
    (``moe_slots_held``), the busiest held expert over the mean
    (``moe_load_max``) and routed-but-not-computed slots (``moe_dropped``,
    0 by construction).
    """
    b, s, d = x.shape
    e, k, held = cfg.n_experts, cfg.experts_per_token, cfg.experts_held
    t = b * s
    xt = x.reshape(t, d)
    with phase("moe-dispatch"):
        logits = jnp.matmul(xt.astype(jnp.float32),
                            params["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        scores = (jax.nn.sigmoid(logits) if cfg.router_scoring == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        top_s, top_e = jax.lax.top_k(scores, k)                  # (T, k)
        wts = top_s / jnp.sum(top_s, -1, keepdims=True) * cfg.routed_scaling
        local = top_e.reshape(-1) - cfg.expert_shard * held       # (T*k,)
        mine = (local >= 0) & (local < held)
        group = jnp.where(mine, local, held)
        order = jnp.argsort(group, stable=True)     # held slots first, by expert
        sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
        tok = order // k
        xs = xt[tok]                                              # (T*k, D)
        ws = jnp.where(mine, wts.reshape(-1), 0.0)[order]
    with phase("expert-compute"):
        hidden = (jax.nn.silu(_gmm(xs, params["wg"], sizes))
                  * _gmm(xs, params["wi"], sizes))
        ys = _gmm(hidden, params["wo"], sizes)
    with phase("moe-dispatch"):
        out = jnp.zeros((t, d), jnp.float32).at[tok].add(ys * ws[:, None])
    out = out.astype(x.dtype)
    if cfg.n_shared_experts:
        out = out + mlp(params["shared"], xt, "swiglu")
    slots = jnp.sum(sizes)
    stats = {"moe_slots_held": slots,
             "moe_load_max": jnp.max(sizes) * held / jnp.maximum(slots, 1),
             "moe_dropped": jnp.sum(mine.astype(jnp.int32)) - slots}
    return out.reshape(b, s, d), stats


def moe(params, x, cfg):
    """Top-k MoE with fixed per-expert capacity and scatter dispatch.

    x: (B, S, D). Returns (out, aux_loss). Dispatch uses scatter-add (no dense
    one-hot einsum) so compiled FLOPs stay ~= active-expert FLOPs.
    """
    b, s, d = x.shape
    e, k, ff = cfg.n_experts, cfg.experts_per_token, cfg.moe_d_ff
    t = b * s
    xt = x.reshape(t, d)
    logits = (xt @ params["router"]).astype(jnp.float32)          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                        # (T, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    cap = max(1, int(cfg.capacity_factor * t * k / e))
    flat_e = top_e.reshape(-1)                                    # (T*k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)           # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0) * onehot                     # 1-based slot
    slot = jnp.sum(pos, axis=-1) - 1                              # (T*k,)
    keep = slot < cap
    slot = jnp.where(keep, slot, cap - 1)

    buf = jnp.zeros((e, cap, d), xt.dtype)
    src = jnp.repeat(xt, k, axis=0) * keep[:, None].astype(xt.dtype)
    buf = buf.at[flat_e, slot].add(src)                           # dispatch
    buf = shard_spec(buf, ["model", None, None])                  # expert parallel

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["wg"])) * \
        jnp.einsum("ecd,edf->ecf", buf, params["wi"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["wo"])         # (E, cap, D)

    gathered = out_buf[flat_e, slot]                              # (T*k, D)
    gathered = gathered * (keep[:, None] * top_p.reshape(-1)[:, None]).astype(xt.dtype)
    out = jnp.sum(gathered.reshape(t, k, d), axis=1)

    # load-balance auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)

    if cfg.dense_residual:
        out = out + mlp(params["dense"], xt, "swiglu")
    return out.reshape(b, s, d), aux


def moe_expert_parallel(params, x, cfg):
    """Expert-parallel MoE via shard_map (§Perf iteration 5's proper fix).

    Tokens are data-sharded and *replicated over the model axis*; experts are
    model-sharded. Each (data, model) shard therefore already holds every
    token it needs: it dispatches its local tokens to its local experts and
    the combine is a single psum over "model" — the 750 GB/chip dispatch
    all-gather GSPMD emits for the global scatter (EXPERIMENTS.md §Perf #5)
    disappears entirely; the remaining collective is one (B,S,d) psum per
    layer, the same shape a dense FFN partial-sum costs.

    Requires expert weights to fit per chip at E/M (true for qwen3-moe's
    768-wide experts; arctic-480b needs the 2-D expert2d layout instead).
    Falls back to the GSPMD path outside a mesh (smoke tests).
    """
    mesh = _ambient_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return moe(params, x, cfg)

    m_size = mesh.shape["model"]
    if cfg.n_experts % m_size:
        return moe(params, x, cfg)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    e_loc = cfg.n_experts // m_size

    def local(router, wi, wg, wo, dense, xl):
        b, s, d = xl.shape
        e, k, ff = cfg.n_experts, cfg.experts_per_token, cfg.moe_d_ff
        t = b * s
        xt = xl.reshape(t, d)
        logits = (xt @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        off = jax.lax.axis_index("model") * e_loc
        flat_e = top_e.reshape(-1) - off                     # local expert ids
        mine = (flat_e >= 0) & (flat_e < e_loc)
        flat_e = jnp.clip(flat_e, 0, e_loc - 1)
        cap = max(1, int(cfg.capacity_factor * t * k / e))
        onehot = jax.nn.one_hot(flat_e, e_loc, dtype=jnp.int32) \
            * mine[:, None].astype(jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) * onehot
        slot = jnp.sum(pos, axis=-1) - 1
        keep = mine & (slot >= 0) & (slot < cap)
        slot = jnp.clip(slot, 0, cap - 1)

        buf = jnp.zeros((e_loc, cap, d), xt.dtype)
        src = jnp.repeat(xt, k, axis=0) * keep[:, None].astype(xt.dtype)
        buf = buf.at[flat_e, slot].add(src)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
            jnp.einsum("ecd,edf->ecf", buf, wi)
        out_buf = jnp.einsum("ecf,efd->ecd", h, wo)
        gathered = out_buf[flat_e, slot]
        gathered = gathered * (keep[:, None] * top_p.reshape(-1)[:, None]
                               ).astype(xt.dtype)
        out = jnp.sum(gathered.reshape(t, k, d), axis=1)
        out = jax.lax.psum(out, "model")                     # the combine

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), axis=0)
        aux = e * jnp.sum(me * ce)
        if cfg.dense_residual:
            out = out + mlp(dense, xt, "swiglu")
        return out.reshape(b, s, d), aux

    pspec = P(*([batch_axes] if batch_axes else [None]), None, None)
    dense = params.get("dense")
    dense_spec = (jax.tree.map(lambda _: P(None, None), dense)
                  if dense is not None else None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None), P("model", None, None), P("model", None, None),
                  P("model", None, None), dense_spec, pspec),
        out_specs=(pspec, P()),
        check_vma=False)
    return fn(params["router"], params["wi"], params["wg"], params["wo"],
              dense, x)
