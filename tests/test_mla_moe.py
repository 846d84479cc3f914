"""Moonlight-16B-A3B's block (latent attention, a dense first layer, dropless
held-expert MoE with shared experts) and the blocked client sum, against
the plain float32 reference ``bench/reference/moonlight.py`` (imported by
path; it imports nothing of the program), at smoke size on the CPU.

Tolerances: the program's grouped expert products take bfloat16 operands
(one MXU pass, as the configuration states) and the reference computes in
float32, so a routed expert's output carries ~2^-9 relative rounding per
product; everything else is float32 on the CPU. Losses agree to 1e-4
relative, gradients leaf by leaf to 2e-2 of the leaf's norm. Sums over
blocks of clients and over one block agree to 1e-5 in norm.
"""
import dataclasses
import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import FLConfig
from repro.configs.registry import get_config
from repro.core import fed, topology
from repro.models import layers as L
from repro.models import transformer

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
_spec = importlib.util.spec_from_file_location(
    "moonlight_reference", ROOT / "bench" / "reference" / "moonlight.py")
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

T = 32


def cfg_small(**kw):
    """Moonlight's smoke variant: 1 dense + 2 MoE layers, 8 experts routed
    over (top 3), 4 held, 2 shared, MLA with 32 + 16 q.k and 32 v dims."""
    base = get_config("moonlight-16b-a3b").smoke(
        n_layers=3, n_experts=8, experts_per_token=3, experts_held=4,
        vocab_size=128, remat=False)
    return dataclasses.replace(base, **kw)


def hf(c):
    """The reference's HF-style view of a program config."""
    return {"num_attention_heads": c.n_heads,
            "qk_nope_head_dim": c.qk_nope_head_dim,
            "kv_lora_rank": c.kv_lora_rank, "rope_theta": c.rope_theta,
            "rms_norm_eps": c.norm_eps, "router_experts": c.n_experts,
            "num_experts_per_tok": c.experts_per_token,
            "n_routed_experts": c.experts_held,
            "routed_scaling_factor": c.routed_scaling,
            "expert_shard": c.expert_shard}


def params_for(c, seed=0):
    """Seeded weights, every matrix N(0, 0.05^2) and norm scales 0.1 N(0, 1)
    (so the (1 + scale) weight is exercised)."""
    shapes = jax.eval_shape(lambda: transformer.init(jax.random.PRNGKey(0), c))
    paths, tree = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.PRNGKey(seed)
    leaves = [(0.1 if str(getattr(p[-1], "key", "")) == "scale" else 0.05)
              * jax.random.normal(jax.random.fold_in(key, i), s.shape)
              for i, (p, s) in enumerate(paths)]
    return jax.tree_util.tree_unflatten(tree, leaves)


def tokens(c, n=2, seed=1):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (n, T + 1), 0,
                              c.vocab_size)
    return toks[:, :-1], toks[:, 1:]


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


def test_loss_and_grads_match_reference():
    c = cfg_small()
    p = params_for(c)
    x, y = tokens(c)
    prog = jax.jit(lambda q: transformer.per_sequence_loss(q, x, y, c))
    prog_loss, stats = prog(p)
    ref_losses = jax.jit(lambda q: jnp.stack(
        [ref.sequence_loss(q, x[i], y[i], hf(c)) for i in range(x.shape[0])]))
    with jax.default_matmul_precision("highest"):
        ref_loss = ref_losses(p)
    np.testing.assert_allclose(prog_loss, ref_loss, rtol=1e-4)
    assert int(stats["moe_dropped"]) == 0
    assert int(stats["moe_slots_held"]) > 0

    g_prog = jax.jit(jax.grad(lambda q: jnp.sum(prog(q)[0])))(p)
    with jax.default_matmul_precision("highest"):
        g_ref = jax.jit(jax.grad(lambda q: jnp.sum(ref_losses(q))))(p)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g_prog)[0],
                            jax.tree.leaves(g_ref)):
        assert rel(a, b) < 2e-2, (jax.tree_util.keystr(path), rel(a, b))


def test_expert_shares_sum_to_the_uncut_layer():
    """Each of 8 chips holds one expert: their outputs, with the shared
    experts (which every chip computes) counted once, add up to the layer
    that holds all 8, and to the reference's."""
    c_all = cfg_small(experts_held=8)
    lp = params_for(c_all)["layers"]
    moe = jax.tree.map(lambda a: a[0], lp["moe"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, T, c_all.d_model))
    whole, _ = L.moe_held(moe, x, c_all)
    shared = L.mlp(moe["shared"], x.reshape(-1, c_all.d_model), "swiglu")
    total = -7 * shared.reshape(x.shape)
    for r in range(8):
        c_r = dataclasses.replace(c_all, experts_held=1, expert_shard=r)
        share = dict(moe, **{k: moe[k][r:r + 1] for k in ("wi", "wg", "wo")})
        total = total + L.moe_held(share, x, c_r)[0]
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.moe(moe, x[i], hf(c_all), "f32")
                          for i in range(2)])
    assert rel(whole, want) < 1e-2


def _skewed_router(c):
    """Router columns that send every token (positive activations) to
    experts 0 .. k-1: all slots land on k of the experts."""
    r = jnp.zeros((c.d_model, c.n_experts))
    return r.at[:, :c.experts_per_token].set(
        jnp.linspace(1.0, 0.5, c.experts_per_token)[None, :])


def test_dropless_layer_keeps_every_slot_where_capacity_drops():
    c = cfg_small(experts_held=8, n_shared_experts=0)
    moe = jax.tree.map(lambda a: a[0], params_for(c)["layers"]["moe"])
    moe = dict(moe, router=_skewed_router(c))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (1, T, c.d_model)))
    out, stats = L.moe_held(moe, x, c)
    assert int(stats["moe_slots_held"]) == T * c.experts_per_token
    assert int(stats["moe_dropped"]) == 0
    with jax.default_matmul_precision("highest"):
        want = ref.moe(dict(moe, shared=None), x[0], hf(c), "f32",
                       fault="no_shared")
    assert rel(out[0], want) < 1e-2
    # the capacity layer, same routing (softmax scores, unscaled): equal to
    # the dropless one when capacity holds every slot, not when it drops
    soft = dataclasses.replace(c, router_scoring="softmax", routed_scaling=1.0)
    dropless, _ = L.moe_held(moe, x, soft)
    roomy, _ = L.moe(moe, x, dataclasses.replace(soft, capacity_factor=8.0))
    dropping, _ = L.moe(moe, x, soft)
    assert rel(roomy, dropless) < 1e-2
    assert rel(dropping, dropless) > 0.1


def test_unwritten_grouped_rows_stay_out(monkeypatch):
    """The TPU's grouped-product kernel leaves the rows past the held slots
    unwritten: filled with NaN here, the layer's output and gradients stay
    finite and unchanged."""
    c = cfg_small()
    moe = jax.tree.map(lambda a: a[0], params_for(c)["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(6), (1, T, c.d_model))

    def out_and_grad():
        f = lambda m, x: jnp.sum(L.moe_held(m, x, c)[0] ** 2)
        return f(moe, x), jax.grad(f, argnums=(0, 1))(moe, x)

    want = out_and_grad()
    ragged = jax.lax.ragged_dot

    def unwritten(lhs, rhs, sizes, **kw):
        y = ragged(lhs, rhs, sizes, **kw)
        rows = jnp.arange(y.shape[0])[:, None] < jnp.sum(sizes)
        return jnp.where(rows, y, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten)
    got = out_and_grad()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8)


def test_mla_decode_matches_the_full_forward():
    """Prefill then one decode step through the latent cache give the
    full forward's last-position logits."""
    c = cfg_small(attention_impl="dot")
    p = params_for(c)
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 17), 0, c.vocab_size)
    _, cache = transformer.prefill(p, {"tokens": toks[:, :16]}, c)
    cache = jax.tree.map(
        lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, 4)] + [(0, 0)] * (a.ndim - 3)),
        cache)
    dec, _ = transformer.decode_step(p, cache, toks[:, 16:], jnp.int32(16), c)
    full, _ = transformer.prefill(p, {"tokens": toks}, c)
    np.testing.assert_allclose(dec[:, -1], full[:, -1], rtol=1e-4, atol=1e-4)


def test_client_block_sizes():
    """The MLP cells' cohort (S = 256, P = 101 632) is one block; the LM
    cell's silos (P = 568.5 M) go one at a time."""
    assert topology.client_block(256, 4 * 101_632) == 256
    assert topology.client_block(8, 4 * 568_500_000) == 1
    assert topology.client_block(12, 10, budget=45) == 4


def _mlp_round(codec, blocks, monkeypatch):
    from repro.comm import make_codec
    from repro.comm.error_feedback import ef_store_init
    from repro.data.synthetic import VirtualFedData
    from repro.models import mlp
    p_dim = 784 * 128 + 128 * 10
    if blocks:
        monkeypatch.setattr(topology, "UPLOAD_BLOCK_SHARE",
                            64 * 4 * p_dim / topology.device_bytes())
    data = VirtualFedData(jax.random.PRNGKey(7), 1000, num_features=784,
                          num_classes=10, noise=4.0)
    params = mlp.init(jax.random.PRNGKey(8), 784, 128, 10)
    ef = ef_store_init(1000, p_dim) if codec else None
    return fed.cohort_round(mlp.per_sample_loss, params, data,
                            jax.random.PRNGKey(9), 16, 256,
                            codec=make_codec(codec) if codec else None, ef=ef)


@pytest.mark.parametrize("codec", [None, "int8"])
def test_blocked_sum_equals_one_block(codec, monkeypatch):
    """Four blocks of 64 clients against one of 256. XLA tiles a vmap of 64
    and of 256 clients differently, so a client's upload differs in its
    last bits; with int8 that flips a few stochastic roundings. The wire
    format keeps its shapes and dtypes (the same bytes)."""
    g1, v1, up1 = _mlp_round(codec, False, monkeypatch)
    g4, v4, up4 = _mlp_round(codec, True, monkeypatch)
    assert up1["q_grad_sums"] is not None and up4["q_grad_sums"] is None
    np.testing.assert_allclose(v4, v1, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g4), jax.tree.leaves(g1)):
        assert rel(a, b) < 1e-5
    if codec:
        for a, b in zip(jax.tree.leaves(up4["encoded"]),
                        jax.tree.leaves(up1["encoded"])):
            assert a.shape == b.shape and a.dtype == b.dtype
        codes4, codes1 = (jax.tree.leaves(u["encoded"])[0] for u in (up4, up1))
        assert codes1.dtype == jnp.int8
        same = codes4 == codes1
        assert float(jnp.mean(~same)) < 1e-4
        # a client's residual moves only where its code flipped
        ids = up1["cohort"]
        r4, r1 = up4["ef"].data[ids], up1["ef"].data[ids]
        gap = jnp.max(jnp.where(same, jnp.abs(r4 - r1), 0.0))
        assert float(gap) < 1e-5 * float(jnp.max(jnp.abs(r1)))


@pytest.mark.parametrize("blocks", [False, True])
def test_lm_cohort_round_matches_reference_round(blocks, monkeypatch):
    """One cohort round of the LM client (4 silos, all drawn) against the
    reference's round: the weighted gradient and loss estimate."""
    from repro.data.synthetic import VirtualTokenData
    c = cfg_small()
    p = params_for(c)
    if blocks:
        monkeypatch.setattr(topology, "UPLOAD_BLOCK_SHARE", 1e-12)
    sizes = {"n_min": 4, "n_max": 16}
    key = jax.random.PRNGKey(11)
    data = VirtualTokenData(key, 4, T, c.vocab_size, **sizes)
    rk = jax.random.PRNGKey(12)
    g, v, up = jax.jit(lambda q: fed.cohort_round(
        lambda q, z, y: transformer.per_sequence_loss(q, z, y, c), q, data,
        rk, 1, 4))(p)
    assert int(jnp.sum(up["client_stats"]["moe_dropped"])) == 0
    silos = ref.Silos(key, 4, T, c.vocab_size, **sizes)
    fl = FLConfig(tau=1.0, l2_lambda=0.0, a1=0.9, a2=0.5)
    out = ref.cohort_rounds(silos, p, [rk], fl.__dict__, 4, 1, hf(c))
    np.testing.assert_allclose(float(v), out["loss"][0], rtol=1e-4)
    flat_g = {"/".join(str(getattr(k, "key", k)) for k in path): a
              for path, a in jax.tree_util.tree_flatten_with_path(g)[0]}
    for k, a in flat_g.items():
        assert abs(float(jnp.linalg.norm(a)) - out["grad"][k]) <= \
            2e-2 * out["grad"][k], k
