"""Plain float32 reference of Moonlight-16B-A3B (the DeepSeek-V3 block,
hf:moonshotai/Moonlight-16B-A3B config.json) as the client model of the
paper's sample-based Algorithm 1 over cross-silo clients. It imports
nothing of the program under test; matrix products run at full float32
precision (``jax.default_matmul_precision("highest")`` and HIGHEST on each
product) unless a control asks for less.

The model, per token x (hidden 2048):

* MLA, no query compression: q = x Wq, H = 16 heads of 128 no-RoPE + 64
  RoPE dims; [c_kv (512), k_pe (64)] = x Wkv_a, c_kv = RMSNorm(c_kv);
  [k_nope (128), v (128)] = c_kv Wkv_b per head; k_pe is shared by all
  heads; RoPE (theta 50 000) on q_pe and k_pe; scores scale 1/sqrt(192),
  causal; out = concat_h(attn_h) Wo.
* Layer 0: dense SwiGLU of width 11 264. Later layers, MoE: s =
  sigmoid(x W_r) over 64 experts (the gate in float32), the top 6 of s + b
  (b, ``e_score_correction_bias``, is 0 here), weights s[top] / sum s[top]
  * 2.446; y = sum over the top experts this chip holds of w_e SwiGLU_e(x)
  (width 1408), plus the shared SwiGLU (width 2 x 1408).
* RMSNorm eps 1e-5 before attention and FFN and at the end; untied head;
  no embedding scale; loss = per-sequence mean next-token cross-entropy.

Departures from the published model, each deliberate:

* RoPE in the half-split layout (dims [0, 32) and [32, 64) rotate as
  pairs) where the published model interleaves pairs: a fixed permutation
  of Wq's and Wkv_a's RoPE columns, which random weights cannot tell apart.
* Norm weights are stored as offsets from 1 (weight = 1 + scale, scale 0
  at the published init of weight 1), the program's layout.
* The chip's share: the layer holds experts [shard * held, +held) of 64
  (``experts_held``); slots routed to the other experts add nothing here,
  as in the program. The vocabulary is a slice (``vocab_size``).
* Attention runs over query blocks of ``QBLOCK`` rows so that no (H, T, T)
  array is held; each block is recomputed in the backward pass.

Parameters come in the program's layout: ``embed`` (V, D), ``unembed``
(D, V), ``ln_f``, ``dense_layers`` (the leading dense layers, stacked) and
``layers`` (the MoE layers, stacked), each layer with ``ln1``, ``attn``
{wq, wkv_a, kv_norm, wkv_b, wo}, ``ln2`` and ``mlp`` {wi, wg, wo} or
``moe`` {router, wi, wg, wo (held experts), shared}.

``precision``: ``"f32"`` computes every product in float32; the controls
round the operands (and, backwards, the cotangent) to ``"bf16"`` (the
program's one bfloat16 pass) or to ``"fp8"`` (float8 e4m3 with a scale
per tensor, the next precision below bfloat16), accumulating in float32.
``fault`` plants one error the check must catch: ``"capacity"`` (slots
beyond T k / E per expert dropped, in token order), ``"no_shared"`` (the
shared experts left out), ``"offset"`` (the next shard's expert range
with this shard's weights), ``"rope_nope"`` (RoPE on the no-RoPE dims),
``"half_silos"`` (the second half of each round's silos left out of the
server's sum).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from bench.reference.mlp_fl import cohort_ids

HI = jax.lax.Precision.HIGHEST
QBLOCK = 512
FP8 = jnp.float8_e4m3fn


def _round_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _round_fp8(x):
    scale = jnp.max(jnp.abs(x)) / float(jnp.finfo(FP8).max)
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


ROUND = {"bf16": _round_bf16, "fp8": _round_fp8}


@functools.lru_cache(maxsize=None)
def _rounded_einsum(spec: str, mode: str):
    rnd = ROUND[mode]

    def exact(a, b):
        return jnp.einsum(spec, a, b, precision=HI)

    @jax.custom_vjp
    def f(a, b):
        return exact(rnd(a), rnd(b))

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        return jax.vjp(exact, rnd(a), rnd(b))[1](rnd(g))

    f.defvjp(fwd, bwd)
    return f


def mm(spec: str, a, b, precision: str):
    """einsum ``spec`` of a and b at ``precision``."""
    if precision == "f32":
        return jnp.einsum(spec, a, b, precision=HI)
    return _rounded_einsum(spec, precision)(a, b)


def rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * (1.0 + scale)


def rope(x, theta):
    """x: (T, ..., d) over positions 0..T-1, half-split pairs."""
    t, half = x.shape[0], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (half,))
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def swiglu(p, x, precision):
    h = (jax.nn.silu(mm("td,df->tf", x, p["wg"], precision))
         * mm("td,df->tf", x, p["wi"], precision))
    return mm("tf,fd->td", h, p["wo"], precision)


def attention(p, x, c, precision, fault=None):
    t = x.shape[0]
    h, nope, r = c["num_attention_heads"], c["qk_nope_head_dim"], c["kv_lora_rank"]
    theta = c["rope_theta"]
    q = mm("td,de->te", x, p["wq"], precision).reshape(t, h, -1)
    kva = mm("td,de->te", x, p["wkv_a"], precision)
    c_kv = rmsnorm(kva[:, :r], p["kv_norm"]["scale"], c["rms_norm_eps"])
    k_pe = kva[:, r:]
    kv = mm("tr,re->te", c_kv, p["wkv_b"], precision).reshape(t, h, -1)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    k_nope, v = kv[..., :nope], kv[..., nope:]
    if fault == "rope_nope":
        q_nope, k_nope = rope(q_nope, theta), rope(k_nope, theta)
    else:
        q_pe, k_pe = rope(q_pe, theta), rope(k_pe, theta)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None, :], (t, h, k_pe.shape[-1]))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    scale = 1.0 / np.sqrt(q.shape[-1])
    nb = t // QBLOCK if t % QBLOCK == 0 and t > QBLOCK else 1
    qb = q.reshape(nb, t // nb, h, -1)

    @jax.checkpoint
    def block(args):
        i, qi = args
        rows = i * qi.shape[0] + jnp.arange(qi.shape[0])
        s = mm("qhd,khd->hqk", qi, k, precision) * scale
        s = jnp.where(jnp.arange(t)[None, None, :] <= rows[None, :, None],
                      s, -jnp.inf)
        return mm("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision)

    o = jax.lax.map(block, (jnp.arange(nb), qb)).reshape(t, -1)
    return mm("te,ed->td", o, p["wo"], precision)


def moe(p, x, c, precision, fault=None):
    e, k = c["router_experts"], c["num_experts_per_tok"]
    held = c["n_routed_experts"]
    t = x.shape[0]
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", x, p["router"], precision=HI))
    top_s, top_e = jax.lax.top_k(s, k)
    w = top_s / jnp.sum(top_s, -1, keepdims=True) * c["routed_scaling_factor"]
    dense_w = jnp.zeros((t, e + held)).at[jnp.arange(t)[:, None], top_e].set(w)
    off = c["expert_shard"] * held + (held if fault == "offset" else 0)
    w_held = dense_w[:, off:off + held]           # (T, held); 0 past expert e
    if fault == "capacity":
        w_held = jnp.where(jnp.cumsum(w_held > 0, 0) <= t * k // e, w_held, 0.0)
    # every held expert on every token, weighted by its routing weight (0
    # where the token did not pick it)
    hidden = (jax.nn.silu(mm("td,edf->etf", x, p["wg"], precision))
              * mm("td,edf->etf", x, p["wi"], precision))
    y = jnp.einsum("te,etd->td", w_held,
                   mm("etf,efd->etd", hidden, p["wo"], precision), precision=HI)
    if fault != "no_shared":
        y = y + swiglu(p["shared"], x, precision)
    return y


def layer(p, x, c, precision, fault=None):
    eps = c["rms_norm_eps"]
    x = x + attention(p["attn"], rmsnorm(x, p["ln1"]["scale"], eps), c,
                      precision, fault)
    y = rmsnorm(x, p["ln2"]["scale"], eps)
    if "mlp" in p:
        return x + swiglu(p["mlp"], y, precision)
    return x + moe(p["moe"], y, c, precision, fault)


def sequence_loss(params, tokens, targets, c, precision="f32", fault=None):
    """Mean next-token cross-entropy of one (T,) token row."""
    x = params["embed"][tokens]

    @jax.checkpoint
    def body(x, lp):
        return layer(lp, x, c, precision, fault), None

    for stack in ("dense_layers", "layers"):
        x, _ = jax.lax.scan(body, x, params[stack])
    x = rmsnorm(x, params["ln_f"]["scale"], c["rms_norm_eps"])
    logits = mm("td,dv->tv", x, params["unembed"], precision)
    gold = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


class Silos:
    """The reference's own copy of the cell's population, written from its
    stated random choices: silo i holds N_i = min(n_max, floor(n_min /
    (1 - u)^(1/tail))) packed rows, u ~ U[0, 1) from fold_in(fold_in(key,
    i), 2); its successor table (vocab, fanout) from fold_in(ck, 1); row r
    starts at randint(fold_in(kr, 0)) and takes successor
    randint(fold_in(kr, 1), (T,)) at each step, kr = fold_in(fold_in(ck,
    3), r); tokens = row[:-1], targets = row[1:]."""

    def __init__(self, key, num_clients, seq_len, vocab_size, n_min, n_max,
                 tail=1.0, fanout=4):
        self.key, self.num_clients = key, int(num_clients)
        self.seq_len, self.vocab = int(seq_len), int(vocab_size)
        self.n_min, self.n_max = n_min, n_max
        self.tail, self.fanout = float(tail), int(fanout)
        self.total = int(sum(self.count(i) for i in range(self.num_clients)))

    def count(self, i):
        u = jax.random.uniform(jax.random.fold_in(
            jax.random.fold_in(self.key, i), 2))
        n = jnp.floor(self.n_min / (1.0 - u) ** (1.0 / self.tail))
        return int(jnp.minimum(n, self.n_max))

    def row(self, i, r):
        ck = jax.random.fold_in(self.key, i)
        succ = jax.random.randint(jax.random.fold_in(ck, 1),
                                  (self.vocab, self.fanout), 0, self.vocab)
        kr = jax.random.fold_in(jax.random.fold_in(ck, 3), r)
        tok = jax.random.randint(jax.random.fold_in(kr, 0), (), 0, self.vocab)
        picks = np.asarray(jax.random.randint(jax.random.fold_in(kr, 1),
                                              (self.seq_len,), 0, self.fanout))
        succ = np.asarray(succ)
        seq = [int(tok)]
        for p in picks:
            seq.append(int(succ[seq[-1], p]))
        seq = jnp.asarray(seq, jnp.int32)
        return seq[:-1], seq[1:]


def _flat(tree):
    return {"/".join(str(getattr(k, "key", k)) for k in path): v
            for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def cohort_rounds(silos, params0, round_keys, fl, participation, batch, c,
                  precision="f32", fault=None):
    """Run len(round_keys) rounds of Algorithm 1 from ``params0`` (program
    layout, float32; host or device arrays), round t at the paper's rho^t
    and gamma^t: S silos drawn by the keyed Feistel permutation of
    ``mlp_fl.cohort_ids``; silo i draws B row indices in [0, N_i) from
    ``fold_in(key, i)`` and uploads q_i = sum over its B_i = min(B, N_i)
    rows of the gradient of the row's loss; the server sums w_i q_i, w_i =
    (I/S) N_i / (B_i N), one silo at a time, then applies eqs. (9), (10),
    (5). Returns each round's loss estimate, ``surrogate`` = ||g^1 + 2
    lambda w^0||, the per-leaf norms of round 1's aggregated gradient
    (``grad``) and of the change over all rounds (``delta``), and the final
    weights (``params``, host arrays), leaves named by their path."""
    lam, tau = fl["l2_lambda"], fl["tau"]
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(jnp.array, params0)           # a copy: w is donated
        grad_fn = jax.jit(jax.value_and_grad(
            lambda p, x, y: sequence_loss(p, x, y, c, precision, fault)))
        axpy = jax.jit(lambda acc, g, a: jax.tree.map(
            lambda u, v: u + a * v, acc, g), donate_argnums=0)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def ssca(w, g, gh, rho, gamma):
            g = jax.tree.map(lambda b, q, x: (1 - rho) * b + rho * (
                q + (2 * lam - 2 * tau) * x), g, gh, w)
            w = jax.tree.map(lambda x, b: (1 - gamma) * x + gamma * (
                -b / (2 * tau)), w, g)
            return w, g

        g = jax.tree.map(jnp.zeros_like, w)
        losses, first, surrogate = [], None, None
        num = silos.num_clients
        for t, key in enumerate(round_keys, start=1):
            ids = cohort_ids(jax.random.fold_in(key, 0x5CA), num, participation)
            gh = jax.tree.map(jnp.zeros_like, w)
            loss = 0.0
            for i in (ids[:len(ids) // 2] if fault == "half_silos" else ids):
                n_i = silos.count(int(i))
                b_i = min(batch, n_i)
                rows = np.asarray(jax.random.randint(
                    jax.random.fold_in(key, int(i)), (batch,), 0, n_i))[:b_i]
                wi = (num / len(ids)) * n_i / (b_i * silos.total)
                for r in rows:
                    x, y = silos.row(int(i), int(r))
                    val, q = grad_fn(w, x, y)
                    gh = axpy(gh, q, jnp.float32(wi))
                    loss += wi * float(val)
            losses.append(loss)
            if t == 1:
                first = {k: float(jnp.linalg.norm(v))
                         for k, v in _flat(gh).items()}
                surrogate = float(jnp.sqrt(sum(
                    jnp.sum((a + 2 * lam * b) ** 2)
                    for a, b in zip(jax.tree.leaves(gh), jax.tree.leaves(w)))))
            rho = 1.0 if t == 1 else min(fl["a1"] / t ** fl["alpha_rho"], 1.0)
            gamma = min(fl["a2"] / t ** fl["alpha_gamma"], 1.0)
            w, g = ssca(w, g, gh, jnp.float32(rho), jnp.float32(gamma))
            del gh
        final = {k: np.asarray(v) for k, v in _flat(w).items()}
    start = _flat(params0)
    delta = {k: float(np.linalg.norm(final[k] - np.asarray(start[k])))
             for k in final}
    return {"loss": losses, "surrogate": surrogate, "grad": first,
            "delta": delta, "params": final}
