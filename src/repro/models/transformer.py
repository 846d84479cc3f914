"""Decoder-only transformer LM: dense (llama/gemma/qwen/glm style), MoE, and
VLM-backbone (prefix-LM over stubbed patch embeddings) variants.

Layer stack is scanned (stacked params, leading L axis) to keep HLO small enough
for 512-virtual-device dry-run compiles on CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L


def _dt(cfg):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_layer(key, cfg, moe=None):
    """One decoder layer; ``moe`` (default: the config has experts) picks
    the expert FFN over the dense one."""
    dt = _dt(cfg)
    moe = bool(cfg.n_experts) if moe is None else moe
    ks = jax.random.split(key, 4)
    attn_init = L.mla_init if cfg.kv_lora_rank else L.attn_init
    p = {
        "ln1": L.rmsnorm_init(cfg.d_model, dt),
        "attn": attn_init(ks[0], cfg, dt),
        "ln2": L.rmsnorm_init(cfg.d_model, dt),
    }
    if moe:
        p["moe"] = L.moe_init(ks[1], cfg, dt)
    else:
        p["mlp"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.activation, dt)
    return p


def _n_dense(cfg):
    """Leading dense-FFN layers of an MoE stack (``first_dense_layers``)."""
    return cfg.first_dense_layers if cfg.n_experts else 0


def init(key, cfg):
    """Stacked layers under ``layers``; an MoE model's leading dense layers
    stack apart under ``dense_layers`` and run first."""
    dt = _dt(cfg)
    k_embed, k_layers, k_out = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    nd = _n_dense(cfg)
    params = {
        "embed": L.embed_init(k_embed, (cfg.vocab_size, cfg.d_model), dt),
        "layers": jax.vmap(lambda k: init_layer(k, cfg))(layer_keys[nd:]),
        "ln_f": L.rmsnorm_init(cfg.d_model, dt),
    }
    if nd:
        params["dense_layers"] = jax.vmap(
            lambda k: init_layer(k, cfg, moe=False))(layer_keys[:nd])
    if not cfg.tie_embeddings:
        params["unembed"] = L.embed_init(k_out, (cfg.d_model, cfg.vocab_size), dt)
    return params


def _stacks(params):
    """The layer stacks in the order they run."""
    return [params[k] for k in ("dense_layers", "layers") if k in params]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _attention(lp, x, positions, cfg, mask):
    if cfg.kv_lora_rank:
        return L.mla_attention(lp["attn"], x, positions, cfg)
    return L.attention(lp["attn"], x, positions, cfg, mask=mask)


def _ffn(lp, y, cfg):
    """(out, aux loss, stats) of a layer's FFN: dense, capacity MoE, or the
    dropless held-expert MoE (which has stats and no aux loss)."""
    if "mlp" in lp:
        return L.mlp(lp["mlp"], y, cfg.activation), jnp.float32(0), {}
    if cfg.experts_held:
        out, stats = L.moe_held(lp["moe"], y, cfg)
        return out, jnp.float32(0), stats
    moe_fn = (L.moe_expert_parallel if cfg.moe_sharding == "expert_parallel"
              else L.moe)
    out, aux = moe_fn(lp["moe"], y, cfg)
    return out, aux, {}


def _block(lp, x, positions, cfg, mask):
    h = x + _attention(lp, L.norm(lp["ln1"], x, cfg), positions, cfg, mask)
    m, aux, stats = _ffn(lp, L.norm(lp["ln2"], h, cfg), cfg)
    return h + m, aux, stats


def backbone(params, x, positions, cfg, mask=None):
    """x: (B, S, D) embedded inputs -> ((B, S, D) final-normed states, aux
    loss, per-layer stats of the MoE stack)."""
    if mask is None and cfg.attention_impl != "chunked" and not cfg.kv_lora_rank:
        mask = L.make_attention_mask(positions, positions, causal=True,
                                     window=cfg.sliding_window)
    # §Perf knob: sequence-parallel residual stream (psum -> reduce-scatter)
    seq_axis = "model" if cfg.seq_shard_activations else None

    def body(carry, lp):
        h, aux = carry
        h, a, stats = _block(lp, h, positions, cfg, mask)
        h = L.shard_batch(h, seq_axis)   # keep clients (= data shards) resident
        return (h, aux + a), stats

    body_fn = jax.checkpoint(body) if cfg.remat else body
    carry = (L.shard_batch(x), jnp.float32(0))
    for stack in _stacks(params):
        carry, stats = jax.lax.scan(body_fn, carry, stack)
    x, aux = carry
    return L.norm(params["ln_f"], x, cfg), aux, stats


def embed(params, tokens, cfg):
    x = params["embed"][tokens].astype(_dt(cfg))
    if cfg.embed_scale:
        x = x * jnp.sqrt(float(cfg.d_model)).astype(_dt(cfg))
    return x


def logits_fn(params, h, cfg):
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    return h @ w.astype(h.dtype)


def _inputs_to_states(params, batch, cfg):
    """Handles plain LM and VLM prefix-LM inputs; returns (h, positions, mask,
    text_start) where loss applies from text_start onwards."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = embed(params, tokens, cfg)
    if cfg.num_prefix_tokens and "prefix_embeddings" in batch:
        pref = batch["prefix_embeddings"].astype(x.dtype)          # (B, Pfx, D)
        x = jnp.concatenate([pref, x], axis=1)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
        mask = L.make_attention_mask(positions, positions, causal=True,
                                     window=cfg.sliding_window,
                                     prefix_len=pref.shape[1])
        return x, positions, mask, pref.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)[None, :]
    return x, positions, None, 0


def per_sequence_loss(params, tokens, targets, cfg):
    """Per-sequence mean next-token cross-entropy of (B, S) token rows, and
    the MoE stack's stats summed (slots, dropped) or maxed (load) over its
    layers: the client loss of a federated LM round."""
    x, positions, _, _ = _inputs_to_states(params, {"tokens": tokens}, cfg)
    h, _, stats = backbone(params, x, positions, cfg)
    logits = logits_fn(params, h, cfg).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    stats = {k: (jnp.max if k.endswith("_max") else jnp.sum)(v)
             for k, v in stats.items()}
    return jnp.mean(logz - gold, axis=-1), stats


def loss_fn(params, batch, cfg):
    """Mean next-token cross-entropy (+ MoE aux). batch: tokens (B,S), targets (B,S)."""
    x, positions, mask, text_start = _inputs_to_states(params, batch, cfg)
    h, aux, _ = backbone(params, x, positions, cfg, mask)
    h = h[:, text_start:, :]
    logits = logits_fn(params, h, cfg).astype(jnp.float32)
    logits = L.shard_batch(logits, None, "model")   # vocab over model axis
    targets = batch["targets"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = jnp.mean(logz - gold)
    return nll + 0.01 * aux / max(1, cfg.n_layers)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def _cache_keys(cfg):
    return ("c_kv", "k_pe") if cfg.kv_lora_rank else ("k", "v")


def init_cache(cfg, batch, max_seq, dtype=None):
    """Per-layer KV cache; MLA caches its latent c_kv and shared k_pe."""
    dt = dtype or _dt(cfg)
    if cfg.kv_lora_rank:
        lead = (cfg.n_layers, batch, max_seq)
        return {"c_kv": jnp.zeros(lead + (cfg.kv_lora_rank,), dt),
                "k_pe": jnp.zeros(lead + (cfg.qk_rope_head_dim,), dt)}
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (cfg.n_layers, batch, max_seq, kv, hd)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _prefill_attention(lp, hn, positions, mask, cfg):
    """(attention output, this layer's cache entries) over a full sequence."""
    if cfg.kv_lora_rank:
        return (L.mla_attention(lp["attn"], hn, positions, cfg),
                L.mla_latent(lp["attn"], hn, positions, cfg))
    q, k, v = L._qkv(lp["attn"], hn, cfg)
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    rep = cfg.n_heads // cfg.n_kv_heads
    if cfg.attention_impl == "chunked":
        bq, sq = hn.shape[0], hn.shape[1]
        if rep > 1:
            kvh, hd = k.shape[2], k.shape[3]
            kf = jnp.broadcast_to(k[:, :, :, None, :],
                                  (bq, sq, kvh, rep, hd)).reshape(bq, sq, cfg.n_heads, hd)
            vf = jnp.broadcast_to(v[:, :, :, None, :],
                                  (bq, sq, kvh, rep, hd)).reshape(bq, sq, cfg.n_heads, hd)
        else:
            kf, vf = k, v
        o = L.chunked_attention(q, kf, vf, positions, positions, causal=True,
                                window=cfg.sliding_window,
                                block=cfg.attention_block)
    else:
        o = L.dot_attention(q, k, v, mask, kv_heads_repeat=rep)
    return o.reshape(hn.shape[0], hn.shape[1], -1) @ lp["attn"]["wo"], (k, v)


def _split_layers(cache, params):
    """The cache's leading layer axis cut into one piece per layer stack."""
    out, at = [], 0
    for stack in _stacks(params):
        n = jax.tree.leaves(stack)[0].shape[0]
        out.append(jax.tree.map(lambda c: c[at:at + n], cache))
        at += n
    return out


def prefill(params, batch, cfg):
    """Full-sequence forward producing last-position logits and a filled cache."""
    x, positions, mask, _ = _inputs_to_states(params, batch, cfg)
    if (mask is None and cfg.attention_impl != "chunked"
            and not cfg.kv_lora_rank):
        mask = L.make_attention_mask(positions, positions, causal=True,
                                     window=cfg.sliding_window)

    def body(h, lp):
        o, entry = _prefill_attention(lp, L.norm(lp["ln1"], h, cfg),
                                      positions, mask, cfg)
        h = h + o
        m, _, _ = _ffn(lp, L.norm(lp["ln2"], h, cfg), cfg)
        return L.shard_batch(h + m), entry

    h, entries = L.shard_batch(x), []
    for stack in _stacks(params):
        h, e = jax.lax.scan(body, h, stack)
        entries.append(e)
    h = L.norm(params["ln_f"], h, cfg)
    logits = logits_fn(params, h[:, -1:, :], cfg)
    cache = {k: jnp.concatenate([e[i] for e in entries])
             for i, k in enumerate(_cache_keys(cfg))}
    return logits, cache


def decode_step(params, cache, token, pos, cfg):
    """One-token decode. token: (B, 1) int32; cache from init_cache/prefill."""
    x = embed(params, token, cfg)
    k0, k1 = _cache_keys(cfg)

    def body(h, inp):
        lp, c0, c1 = inp
        hn = L.norm(lp["ln1"], h, cfg)
        if cfg.kv_lora_rank:
            o, c0, c1 = L.mla_decode(lp["attn"], hn, c0, c1, pos, cfg)
        else:
            o, c0, c1 = L.attention_decode(lp["attn"], hn, c0, c1, pos, cfg,
                                           window=cfg.sliding_window)
        h = h + o
        m, _, _ = _ffn(lp, L.norm(lp["ln2"], h, cfg), cfg)
        return h + m, (c0, c1)

    h, new = x, []
    for stack, c in zip(_stacks(params), _split_layers(cache, params)):
        h, cs = jax.lax.scan(body, h, (stack, c[k0], c[k1]))
        new.append(cs)
    h = L.norm(params["ln_f"], h, cfg)
    logits = logits_fn(params, h, cfg)
    return logits, {k: jnp.concatenate([c[i] for c in new])
                    for i, k in enumerate((k0, k1))}


# ---------------------------------------------------------------------------
# sharding specs
# ---------------------------------------------------------------------------


def param_specs(cfg, mode: str = "train"):
    """PartitionSpec pytree matching init(). mode: train (fsdp|tp) / serve (tp)."""
    policy = cfg.train_sharding if mode == "train" else cfg.serve_sharding
    fsdp = "data" if policy == "fsdp" else None
    kv_shardable = cfg.n_kv_heads % 16 == 0  # can kv-head dim split the model axis?

    attn = {
        "wq": P(None, fsdp, "model"),
        "wk": P(None, fsdp, "model" if kv_shardable else None),
        "wv": P(None, fsdp, "model" if kv_shardable else None),
        "wo": P(None, "model", fsdp),
    }
    if cfg.qkv_bias:
        attn.update({"bq": P(None, "model"),
                     "bk": P(None, "model" if kv_shardable else None),
                     "bv": P(None, "model" if kv_shardable else None)})
    if cfg.kv_lora_rank:
        attn = {"wq": P(None, fsdp, "model"), "wkv_a": P(None, fsdp, None),
                "kv_norm": {"scale": P(None, None)},
                "wkv_b": P(None, None, "model"), "wo": P(None, "model", fsdp)}
    lp = {"ln1": {"scale": P(None, None)}, "ln2": {"scale": P(None, None)}, "attn": attn}
    dense_mlp = {"wi": P(None, fsdp, "model"), "wg": P(None, fsdp, "model"),
                 "wo": P(None, "model", fsdp)}
    dense_lp = dict(lp, mlp=dense_mlp)
    if cfg.n_experts:
        if cfg.moe_sharding == "expert_parallel":
            # experts resident on the model axis, replicated over data
            moe = {
                "router": P(None, None, None),
                "wi": P(None, "model", None, None),
                "wg": P(None, "model", None, None),
                "wo": P(None, "model", None, None),
            }
            if cfg.dense_residual:
                moe["dense"] = {"wi": P(None, None, "model"),
                                "wg": P(None, None, "model"),
                                "wo": P(None, "model", None)}
        elif cfg.moe_sharding == "expert2d":
            # §Perf: expert-parallel (model axis) x ffn-dim (data axis) 2D
            # sharding — weights stay resident, no per-step FSDP all-gathers
            moe = {
                "router": P(None, None, None),
                "wi": P(None, "model", None, "data"),
                "wg": P(None, "model", None, "data"),
                "wo": P(None, "model", "data", None),
            }
        else:
            moe = {
                "router": P(None, fsdp, None),
                "wi": P(None, "model", fsdp, None),
                "wg": P(None, "model", fsdp, None),
                "wo": P(None, "model", None, fsdp),
            }
        if cfg.dense_residual:
            moe["dense"] = {"wi": P(None, fsdp, "model"),
                            "wg": P(None, fsdp, "model"),
                            "wo": P(None, "model", fsdp)}
        if cfg.n_shared_experts:
            moe["shared"] = dense_mlp
        lp["moe"] = moe
    else:
        lp["mlp"] = dict(dense_mlp)
        if cfg.activation == "gelu":
            del lp["mlp"]["wg"]
    specs = {"embed": P("model", fsdp), "layers": lp, "ln_f": {"scale": P(None)}}
    if _n_dense(cfg):
        specs["dense_layers"] = dense_lp
    if not cfg.tie_embeddings:
        specs["unembed"] = P(fsdp, "model")
    return specs


def cache_specs(cfg):
    if cfg.kv_lora_rank:
        spec = P(None, "data", None, None)
        return {"c_kv": spec, "k_pe": spec}
    kv_shardable = cfg.n_kv_heads % 16 == 0
    # batch over data; kv-heads over model when divisible, else sequence over model
    if kv_shardable:
        spec = P(None, "data", None, "model", None)
    else:
        spec = P(None, "data", "model", None, None)
    return {"k": spec, "v": spec}
