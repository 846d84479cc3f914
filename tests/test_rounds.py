"""Scan-round driver + heterogeneous-protocol invariants: scan==loop
trajectories, unbiased partial-participation aggregation, exactly-once
Dirichlet partitioning, and ragged-masked == dense batch selection."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Primitive

from repro.configs.base import FLConfig
from repro.core import algorithms, fed, optimizer, rounds
from repro.models import mlp

P, J, L = 12, 6, 3


def _data(key, n=240):
    z = jax.random.normal(key, (n, P))
    lab = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, L)
    return z, jax.nn.one_hot(lab, L)


def psl(p, z, y):
    return mlp.per_sample_loss(p, z, y)


def _fl(**kw):
    base = dict(batch_size=20, a1=0.9, a2=0.5, alpha_rho=0.1,
                alpha_gamma=0.6, tau=0.2, l2_lambda=1e-5)
    base.update(kw)
    return FLConfig(**base)


# ---------------------------------------------------------------------------
# scan driver == per-round Python loop
# ---------------------------------------------------------------------------


def test_scan_driver_matches_loop_algorithm1():
    z, y = _data(jax.random.PRNGKey(0))
    params0 = mlp.init(jax.random.PRNGKey(1), P, J, L)
    data = fed.partition_samples(z, y, 4)
    fl = _fl()
    kw = dict(key=jax.random.PRNGKey(2), eval_every=0)
    r_scan = algorithms.algorithm1(psl, params0, data, fl, 60, **kw)
    r_loop = algorithms.algorithm1(psl, params0, data, fl, 60, driver="loop",
                                   **kw)
    np.testing.assert_allclose(np.asarray(r_scan.history["round_loss_est"]),
                               np.asarray(r_loop.history["round_loss_est"]),
                               atol=1e-5)
    for a, b in zip(jax.tree.leaves(r_scan.params),
                    jax.tree.leaves(r_loop.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_scan_driver_matches_loop_constrained_and_participation():
    z, y = _data(jax.random.PRNGKey(3))
    params0 = mlp.init(jax.random.PRNGKey(1), P, J, L)
    data = fed.partition_dirichlet(z, y, 5, jax.random.PRNGKey(4), alpha=0.4)
    fl = _fl(constrained=True, cost_limit=1.2, penalty_c=1e4)
    kw = dict(key=jax.random.PRNGKey(2), eval_every=0, participation=2)
    r_scan = algorithms.algorithm2(psl, params0, data, fl, 40, **kw)
    r_loop = algorithms.algorithm2(psl, params0, data, fl, 40, driver="loop",
                                   **kw)
    for k in ("round_loss_est", "round_slack"):
        np.testing.assert_allclose(np.asarray(r_scan.history[k]),
                                   np.asarray(r_loop.history[k]), atol=1e-5)
    # nu's scale is set by penalty_c (up to 1e4), so compare relatively
    np.testing.assert_allclose(np.asarray(r_scan.history["round_nu"]),
                               np.asarray(r_loop.history["round_nu"]),
                               rtol=1e-4, atol=1e-4)


def test_explicit_schedule_inputs_match_state_derived():
    """Threading precomputed rho/gamma through the scan must equal letting
    ssca_step derive them from the carried t (incl. the rho(1)=1 rule)."""
    params0 = mlp.init(jax.random.PRNGKey(1), P, J, L)
    fl = _fl()
    grad = jax.tree.map(jnp.ones_like, params0)
    s_implicit = s_explicit = optimizer.ssca_init(params0)
    rho, gamma = rounds.schedule_arrays(fl, 1, 5)
    for i in range(5):
        s_implicit = optimizer.ssca_step(s_implicit, grad, fl)
        s_explicit = optimizer.ssca_step(s_explicit, grad, fl,
                                         rho_t=rho[i], gamma_t=gamma[i])
    for a, b in zip(jax.tree.leaves(s_implicit.params),
                    jax.tree.leaves(s_explicit.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_rounds_eval_chunking_histories():
    z, y = _data(jax.random.PRNGKey(0))
    params0 = mlp.init(jax.random.PRNGKey(1), P, J, L)
    data = fed.partition_samples(z, y, 4)
    fl = _fl()

    def eval_fn(params, state):
        return {"loss": float(mlp.mean_loss(params, z, y))}

    r = algorithms.algorithm1(psl, params0, data, fl, 40,
                              jax.random.PRNGKey(2), eval_fn=eval_fn,
                              eval_every=10)
    assert r.history["round"].shape == (4,)
    assert r.history["loss"].shape == (4,)
    # full per-round series ride along
    assert r.history["round_loss_est"].shape == (40,)
    np.testing.assert_array_equal(np.asarray(r.history["round_t"]),
                                  np.arange(1, 41))


# ---------------------------------------------------------------------------
# the driver's per-chunk work: one compiled inputs call, history on the host
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sched", [
    {},                                          # the paper's exponents
    {"alpha_rho": 1.0, "alpha_gamma": 0.5},      # exponents XLA specialises
])
@pytest.mark.parametrize("k", [1, 4, 20])
@pytest.mark.parametrize("t_start", [1, 2, 21, 1001])
def test_chunk_inputs_match_eager_formula(t_start, k, sched):
    """The compiled per-chunk program gives bit for bit the driver's eager
    ``split`` + `make_inputs`: keys, ρ (with ρ^1 = 1), γ and t."""
    fl, key = _fl(**sched), jax.random.PRNGKey(7)
    next_key, inp = rounds.chunk_inputs(fl, key, t_start, k)
    want_key, sub = jax.random.split(key)
    want = rounds.make_inputs(fl, t_start, k, sub)
    np.testing.assert_array_equal(np.asarray(next_key), np.asarray(want_key))
    for got, ref in zip(inp, want):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(inp.t),
                                  np.arange(t_start, t_start + k))
    if t_start == 1:
        assert float(inp.rho[0]) == 1.0


def test_chunk_inputs_compile_once_per_chunk_size():
    """Successive chunks of one size (another t_start, another schedule)
    reuse one compiled program."""
    key = jax.random.PRNGKey(0)
    key, _ = rounds.chunk_inputs(_fl(), key, 1, 7)
    rounds.chunk_inputs(_fl(alpha_gamma=0.8), key, 8, 7)
    assert rounds._chunk_inputs_jit(7)._cache_size() == 1


def _eager_driver(step, state, fl, key, num_rounds, eval_fn, every):
    """`run_rounds` as the eager formula: split + make_inputs per chunk, and
    the history assembled with jnp on the device."""
    hist, per_round, t0 = {"round": []}, [], 1
    for size in rounds.chunk_sizes(num_rounds, every):
        key, sub = jax.random.split(key)
        state, ms = rounds.scan_rounds(step, state,
                                       rounds.make_inputs(fl, t0, size, sub))
        t0 += size
        per_round.append(ms)
        for k, v in eval_fn(state.params, state).items():
            hist.setdefault(k, []).append(v)
        hist["round"].append(t0 - 1)
    history = {k: jnp.asarray(v) for k, v in hist.items()}
    for k in per_round[0]:
        history["round_" + k] = jnp.concatenate([m[k] for m in per_round])
    history["round_t"] = jnp.arange(1, t0)
    return history


@pytest.mark.parametrize("every", [12, 3])      # one chunk, four chunks
def test_run_rounds_history_matches_eager_formula(every):
    """Keys, shapes, dtypes and values of the history — eval series from
    Python floats and from device arrays, "round", "round_t" and every
    per-round series — are those of the eager formula."""
    z, y = _data(jax.random.PRNGKey(0))
    params0 = mlp.init(jax.random.PRNGKey(1), P, J, L)
    fl = _fl()
    step = algorithms.make_algorithm1_step(
        psl, fed.partition_samples(z, y, 4), fl)
    state0 = optimizer.ssca_init(params0)

    def eval_fn(params, state):
        return {"loss": float(mlp.mean_loss(params, z, y)),
                "w_norm": jnp.sqrt(sum(jnp.sum(x * x) for x in
                                       jax.tree.leaves(params)))}

    got = rounds.run_rounds(step, state0, fl, jax.random.PRNGKey(2), 12,
                            eval_fn=eval_fn, eval_every=every).history
    want = _eager_driver(step, state0, fl, jax.random.PRNGKey(2), 12,
                         eval_fn, every)
    assert sorted(got) == sorted(want)
    assert got["round"].shape == got["loss"].shape == (12 // every,)
    for k in want:
        assert got[k].shape == want[k].shape, k
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]), err_msg=k)


def test_run_rounds_runs_no_eager_op_between_chunks(monkeypatch):
    """Between one chunk's eval and the next chunk's enqueue, a warmed
    `run_rounds` binds no JAX primitive in Python: the inputs call and the
    K-round program take jit's compiled fast path, and the history of a
    one-chunk call (the benchmark's dispatch) touches no device. A
    multi-chunk call concatenates its per-round series once, at the end,
    under ``rounds/history``."""
    z, y = _data(jax.random.PRNGKey(0))
    fl = _fl()
    step = algorithms.make_algorithm1_step(
        psl, fed.partition_samples(z, y, 4), fl)
    loss = jax.jit(mlp.mean_loss)

    def eval_fn(params, state):
        return {"loss": float(loss(params, z, y))}

    keys = list(jax.random.split(jax.random.PRNGKey(2), 4))

    def run(state, t_start, num_rounds, every):
        return rounds.run_rounds(step, state, fl, keys.pop(), num_rounds,
                                 eval_fn=eval_fn, eval_every=every,
                                 t_start=t_start)

    state = optimizer.ssca_init(mlp.init(jax.random.PRNGKey(1), P, J, L))
    state = run(state, 1, 8, 2).final_state          # warm every shape
    state = run(state, 9, 2, 2).final_state

    spans, binds = [None], []
    span = rounds.obs_trace.host_span

    @contextlib.contextmanager
    def recording_span(name, **attrs):
        spans.append(name)
        try:
            with span(name, **attrs):
                yield
        finally:
            spans.pop()

    bind = Primitive.bind

    def recording_bind(self, *args, **params):
        binds.append((spans[-1], self.name))
        return bind(self, *args, **params)

    monkeypatch.setattr(rounds.obs_trace, "host_span", recording_span)
    monkeypatch.setattr(Primitive, "bind", recording_bind)
    state = run(state, 11, 2, 2).final_state         # one chunk
    assert binds == []
    run(state, 13, 8, 2)                             # four chunks
    assert {s for s, _ in binds} <= {"rounds/history"}, binds
    assert rounds._chunk_inputs_jit(2)._cache_size() == 1


# ---------------------------------------------------------------------------
# partial participation
# ---------------------------------------------------------------------------


def test_participation_mask_uniform_without_replacement():
    I, S = 5, 2
    masks = jax.vmap(lambda k: fed.participation_mask(k, I, S))(
        jax.random.split(jax.random.PRNGKey(0), 4000))
    np.testing.assert_array_equal(np.asarray(jnp.sum(masks, axis=1)),
                                  np.full(4000, S))            # exactly S
    freq = np.asarray(jnp.mean(masks, axis=0))
    np.testing.assert_allclose(freq, S / I, atol=0.03)         # uniform


def test_participation_weights_unbiased():
    """E over the participation draw of the reweighted aggregation weights
    equals the full-participation weights (Horvitz-Thompson)."""
    counts = jnp.array([70, 30, 50, 10], jnp.int32)
    B = 5
    dense_w = fed.aggregation_weights(counts, B)
    masks = jax.vmap(lambda k: fed.participation_mask(k, 4, 2))(
        jax.random.split(jax.random.PRNGKey(1), 20000))
    ws = jax.vmap(lambda m: fed.aggregation_weights(counts, B, m))(masks)
    np.testing.assert_allclose(np.asarray(jnp.mean(ws, axis=0)),
                               np.asarray(dense_w), rtol=0.05)


def test_participation_grad_estimate_unbiased():
    """Averaging sample_round's grad estimate over participation draws (same
    batch key) converges to the full-participation estimate."""
    z, y = _data(jax.random.PRNGKey(0), n=120)
    params = mlp.init(jax.random.PRNGKey(1), P, J, L)
    data = fed.partition_samples(z, y, 4)
    key, B = jax.random.PRNGKey(2), 10
    dense, _, _ = fed.sample_round(psl, params, data, key, B)

    def one(pk):
        g, _, _ = fed.sample_round(psl, params, data, key, B,
                                   participation=2, participation_key=pk)
        return g

    gs = jax.vmap(one)(jax.random.split(jax.random.PRNGKey(3), 600))
    mean_g = jax.tree.map(lambda u: jnp.mean(u, axis=0), gs)
    for a, b in zip(jax.tree.leaves(mean_g), jax.tree.leaves(dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0.15, atol=5e-3)


def test_participation_equal_to_num_clients_is_dense():
    z, y = _data(jax.random.PRNGKey(0), n=120)
    params = mlp.init(jax.random.PRNGKey(1), P, J, L)
    data = fed.partition_samples(z, y, 4)
    dense, _, _ = fed.sample_round(psl, params, data, jax.random.PRNGKey(2), 10)
    same, _, up = fed.sample_round(psl, params, data, jax.random.PRNGKey(2),
                                   10, participation=4)
    assert up["participants"] is None
    for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(same)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Dirichlet non-IID partitioning
# ---------------------------------------------------------------------------


def test_dirichlet_partition_preserves_every_sample_exactly_once():
    n = 500
    z = jnp.arange(n, dtype=jnp.float32)[:, None] * jnp.ones((1, P))
    lab = jax.random.randint(jax.random.PRNGKey(0), (n,), 0, L)
    y = jax.nn.one_hot(lab, L)
    data = fed.partition_dirichlet(z, y, 7, jax.random.PRNGKey(1), alpha=0.3)
    assert int(data.total) == n
    seen = []
    for i in range(7):
        c = int(data.counts[i])
        assert c >= 1
        seen.extend(np.asarray(data.features[i, :c, 0]).astype(int).tolist())
        # padding rows are zero
        assert float(jnp.abs(data.features[i, c:]).sum()) == 0.0
    assert sorted(seen) == list(range(n))


def test_dirichlet_alpha_controls_label_skew():
    z, y = _data(jax.random.PRNGKey(5), n=3000)

    def mean_label_entropy(alpha):
        data = fed.partition_dirichlet(z, y, 10, jax.random.PRNGKey(6),
                                       alpha=alpha)
        ents = []
        for i in range(10):
            c = int(data.counts[i])
            p = np.asarray(jnp.sum(data.labels[i, :c], axis=0)) / c
            ents.append(-(p[p > 0] * np.log(p[p > 0])).sum())
        return np.mean(ents)

    assert mean_label_entropy(0.05) < mean_label_entropy(50.0) - 0.3


# ---------------------------------------------------------------------------
# ragged masked batches
# ---------------------------------------------------------------------------


def test_ragged_masked_matches_dense_when_equal_counts():
    z, y = _data(jax.random.PRNGKey(0), n=240)
    params = mlp.init(jax.random.PRNGKey(1), P, J, L)
    data = fed.partition_samples(z, y, 4)          # all N_i = 60 >= B
    g, v, _ = fed.sample_round(psl, params, data, jax.random.PRNGKey(2), 20)
    # dense reference: unmasked manual aggregation with N_i/(BN)
    idx = fed.sample_batches(data, jax.random.PRNGKey(2), 20)
    zs = jnp.concatenate([data.features[i][idx[i]] for i in range(4)])
    ys = jnp.concatenate([data.labels[i][idx[i]] for i in range(4)])
    ref = jax.grad(lambda p: jnp.mean(psl(p, zs, ys)))(params)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-6)


def test_small_client_batches_are_masked_and_reweighted():
    """A client with N_i < B contributes a B_i = N_i masked batch with weight
    N_i/(B_i·N) — padding rows never leak into the estimate."""
    key = jax.random.PRNGKey(7)
    z, y = _data(key, n=64)
    params = mlp.init(jax.random.PRNGKey(1), P, J, L)
    data = fed.partition_ragged([z[:60], z[60:]], [y[:60], y[60:]])
    assert [int(c) for c in data.counts] == [60, 4]
    B = 16
    g, v, _ = fed.sample_round(psl, params, data, key, B, with_value=True)
    idx = fed.sample_batches(data, key, B)
    mask = fed.batch_mask(data.counts, B)
    w = [60 / (16 * 64), 4 / (4 * 64)]             # N_i/(min(B,N_i)·N)

    def q(i):
        zb = data.features[i][idx[i]]
        yb = data.labels[i][idx[i]]
        return jax.grad(lambda p: jnp.sum(psl(p, zb, yb) * mask[i]))(params)

    ref = jax.tree.map(lambda a, b: w[0] * a + w[1] * b, q(0), q(1))
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-6)


def test_heterogeneous_run_converges():
    """End-to-end: Dirichlet non-IID + partial participation still decreases
    the training cost under the scan driver (Theorem 1 regime)."""
    z, y = _data(jax.random.PRNGKey(8), n=600)
    params0 = mlp.init(jax.random.PRNGKey(1), P, J, L)
    data = fed.partition_dirichlet(z, y, 6, jax.random.PRNGKey(9), alpha=0.3)
    fl = _fl(batch_size=30)

    def eval_fn(params, state):
        return {"loss": float(mlp.mean_loss(params, z, y))}

    r = algorithms.algorithm1(psl, params0, data, fl, 120,
                              jax.random.PRNGKey(2), eval_fn=eval_fn,
                              eval_every=40, participation=3)
    losses = np.asarray(r.history["loss"])
    assert np.isfinite(losses).all()
    assert losses[-1] < float(mlp.mean_loss(params0, z, y))
