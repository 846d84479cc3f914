"""Plain float32 reference of the paper's sample-based Algorithm 1 over a
sampled cohort (optionally with int8 stochastic-rounding uploads and error
feedback) on the paper's two-layer swish network (section V, eq. (28)).

Written from the paper and from the protocol's stated random choices; it
imports nothing of the program under test. Each round: S clients are drawn
without replacement by a keyed 6-round Feistel permutation of the
population (murmur3 finaliser as the round function, cycle walking into
[0, I)); client i draws B row indices in [0, N_i) from ``fold_in(key, i)``
and uses B_i = min(B, N_i) of them; it uploads q_i = sum of its rows' loss
gradients; with int8 it encodes q_i + r_i in 256-float chunks, each scaled
by absmax/127 and rounded stochastically with uniform noise from
``fold_in(fold_in(key, 0xC0DEC), i)``, and keeps the residual; the server
sums w_i * q_i with w_i = (I/S) N_i / (B_i N) and applies eqs. (9), (10),
(5).

``precision="f32"`` computes every matrix product at full float32
precision; the controls compute them one step lower (``"high"``,
``"bf16"``: ``bench/reference/lowp.py``).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from bench.reference.lowp import HI, matmul


def swish(z):
    return z * jax.nn.sigmoid(z)


def per_row_loss(w0, w1, z, y, precision):
    pre = matmul(z, w1.T, precision)
    lg = matmul(swish(pre), w0.T, precision)
    return -jnp.sum(y * jax.nn.log_softmax(lg, -1), -1)


def _mix(x):
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def cohort_ids(key, num_clients: int, cohort: int) -> np.ndarray:
    """The S distinct client ids of a round: the keyed Feistel permutation
    of [0, 2^bits) applied to 0..S-1, each walked until it lands in [0, I)."""
    bits = max(8, (num_clients - 1).bit_length())
    lo_bits, hi_bits = bits // 2, bits - bits // 2
    rk = np.asarray(jax.random.bits(key, (6,), jnp.uint32))
    lo_mask, hi_mask = np.uint32((1 << lo_bits) - 1), np.uint32((1 << hi_bits) - 1)

    def perm(x):
        hi, lo = x >> np.uint32(lo_bits), x & lo_mask
        for r in range(6):
            if r % 2 == 0:
                lo = (lo + _mix(hi ^ rk[r])) & lo_mask
            else:
                hi = (hi + _mix(lo ^ rk[r])) & hi_mask
        return (hi << np.uint32(lo_bits)) | lo

    out = []
    with np.errstate(over="ignore"):
        for i in range(cohort):
            x = perm(np.uint32(i))
            while x >= num_clients:
                x = perm(x)
            out.append(int(x))
    assert len(set(out)) == cohort
    return np.asarray(out, np.int32)


def int8_roundtrip(x, key, chunk: int = 256):
    """Stochastic int8 round trip of one flat upload: (decoded, residual)."""
    p = x.shape[0]
    xc = jnp.pad(x, (0, (-p) % chunk)).reshape(-1, chunk)
    bits = jax.random.bits(key, xc.shape, jnp.uint32)
    u = (bits >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))
    scale = jnp.max(jnp.abs(xc), 1, keepdims=True) * (1.0 / 127)
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.floor(xc / safe + u), -127, 127)
    dec = (q * scale).reshape(-1)[:p]
    return dec, x - dec


def cohort_rounds(pop, params0, round_keys, fl, participation, batch, int8,
                  precision="f32", fault=None):
    """Run len(round_keys) rounds of Algorithm 1 from ``params0`` ({'w0',
    'w1'} float32), round t at the paper's rho^t and gamma^t. Returns each
    round's loss estimate; ``surrogate``, round 1's surrogate step
    ||g^1 + 2 lambda w^0|| over the whole model (what the program's
    stationarity residual reads at round 1, times 2 tau); the per-leaf
    norms of round 1's aggregated gradient (``grad``) and of the change
    over all rounds (``delta``); and the final weights. ``fault`` plants a
    fault the check must catch: 'half_batch' (each client's second half of
    rows left out, the first half counted twice) or 'altered' (the first
    drawn client's upload negated)."""
    lam, tau = fl["l2_lambda"], fl["tau"]
    w0, w1 = params0["w0"], params0["w1"]
    n0 = w0.size
    g0, g1 = jnp.zeros_like(w0), jnp.zeros_like(w1)
    residual = (jnp.zeros((pop.num_clients, n0 + w1.size), jnp.float32)
                if int8 else None)
    num = pop.num_clients
    losses, first, surrogate = [], None, None

    def client(w0, w1, z, y, m):
        return jnp.sum(per_row_loss(w0, w1, z, y, precision) * m)

    grad_fn = jax.jit(jax.vmap(jax.value_and_grad(client, argnums=(0, 1)),
                               in_axes=(None, None, 0, 0, 0)))
    for t, key in enumerate(round_keys, start=1):
        ids = cohort_ids(jax.random.fold_in(key, 0x5CA), num, participation)
        jids = jnp.asarray(ids)
        cnt = pop.counts_for(jids)
        idx = jax.vmap(lambda i, c: jax.random.randint(
            jax.random.fold_in(key, i), (batch,), 0, c))(jids, cnt)
        z, y = pop.batch_rows(jids, idx)
        b_i = jnp.minimum(cnt, batch)
        mask = (jnp.arange(batch)[None, :] < b_i[:, None]).astype(jnp.float32)
        if fault == "half_batch":
            mask = 2.0 * mask * (jnp.arange(batch) < batch // 2)
        vals, (q0, q1) = grad_fn(w0, w1, z, y, mask)
        q = jnp.concatenate([q0.reshape(len(ids), -1).astype(jnp.float32),
                             q1.reshape(len(ids), -1).astype(jnp.float32)], 1)
        if fault == "altered":
            q = q.at[0].multiply(-1.0)
        if int8:
            ck = jax.random.fold_in(key, 0xC0DEC)
            q, rows = jax.vmap(lambda x, i: int8_roundtrip(
                x, jax.random.fold_in(ck, i)))(q + residual[jids], jids)
            residual = residual.at[jids].set(rows)
        w = (num / len(ids)) * cnt.astype(jnp.float32) / (
            b_i.astype(jnp.float32) * pop.total)
        ghat = jnp.matmul(w, q, precision=HI)
        losses.append(float(jnp.dot(w, vals.astype(jnp.float32),
                                    precision=HI)))
        gh0, gh1 = ghat[:n0].reshape(w0.shape), ghat[n0:].reshape(w1.shape)
        if t == 1:
            first = {"w0": float(jnp.linalg.norm(gh0)),
                     "w1": float(jnp.linalg.norm(gh1))}
            surrogate = float(jnp.sqrt(jnp.sum((gh0 + 2 * lam * w0) ** 2)
                                       + jnp.sum((gh1 + 2 * lam * w1) ** 2)))
        rho = 1.0 if t == 1 else min(fl["a1"] / t ** fl["alpha_rho"], 1.0)
        gamma = min(fl["a2"] / t ** fl["alpha_gamma"], 1.0)
        g0 = (1 - rho) * g0 + rho * (gh0 + (2 * lam - 2 * tau) * w0)
        g1 = (1 - rho) * g1 + rho * (gh1 + (2 * lam - 2 * tau) * w1)
        w0 = (1 - gamma) * w0 + gamma * (-g0 / (2 * tau))
        w1 = (1 - gamma) * w1 + gamma * (-g1 / (2 * tau))
    delta = {"w0": float(jnp.linalg.norm(w0 - params0["w0"])),
             "w1": float(jnp.linalg.norm(w1 - params0["w1"]))}
    return {"loss": losses, "surrogate": surrogate, "grad": first,
            "delta": delta, "params": {"w0": np.asarray(w0),
                                       "w1": np.asarray(w1)}}
