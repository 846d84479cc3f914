"""Cohort engine (DESIGN.md §14): O(S) participant-only sampling, keyed EF
store, virtual-population data view, and dense==cohort trajectory equality
for every sample-based driver — including the int8+EF+sharded composition.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import codecs, error_feedback as ef_lib
from repro.configs.base import FLConfig
from repro.core import algorithms, baselines, fed, local_updates
from repro.core import topology as topology_lib
from repro.data.synthetic import VirtualFedData
from repro.models import mlp

P, J, L = 10, 8, 3


def _fl(**kw):
    base = dict(batch_size=6, a1=0.9, a2=0.5, alpha_rho=0.1,
                alpha_gamma=0.6, tau=0.2, l2_lambda=1e-5)
    base.update(kw)
    return FLConfig(**base)


def _virtual(key, num_clients, **kw):
    kw.setdefault("n_min", 6)
    kw.setdefault("n_max", 14)
    kw.setdefault("num_features", P)
    kw.setdefault("num_classes", L)
    return VirtualFedData(key, num_clients, **kw)


def _params(key):
    return mlp.init(key, P, J, L)


def _maxdiff(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# cohort_sample: the keyed Feistel draw
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("num_clients,cohort", [(5, 2), (50, 10), (64, 64),
                                                (1000, 64), (1_000_000, 256)])
def test_cohort_sample_valid_draw(num_clients, cohort):
    ids = fed.cohort_sample(jax.random.PRNGKey(3), num_clients, cohort)
    assert ids.shape == (cohort,)
    assert ids.dtype == jnp.int32
    assert int(jnp.min(ids)) >= 0 and int(jnp.max(ids)) < num_clients
    # a Feistel permutation is a bijection: no duplicates, ever
    assert len(np.unique(np.asarray(ids))) == cohort


def test_cohort_sample_key_sensitivity():
    a = fed.cohort_sample(jax.random.PRNGKey(0), 10_000, 64)
    b = fed.cohort_sample(jax.random.PRNGKey(1), 10_000, 64)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    # and deterministic per key
    c = fed.cohort_sample(jax.random.PRNGKey(0), 10_000, 64)
    assert np.array_equal(np.asarray(a), np.asarray(c))


def test_cohort_sample_rejects_bad_cohort():
    with pytest.raises(ValueError, match="cohort"):
        fed.cohort_sample(jax.random.PRNGKey(0), 10, 11)
    with pytest.raises(ValueError, match="cohort"):
        fed.cohort_sample(jax.random.PRNGKey(0), 10, 0)


def test_cohort_sample_unbiased_selection_frequency():
    """Statistical unbiasedness: over R independent draws each client is
    selected with empirical frequency ≈ S/I, within a 5σ binomial bound."""
    num_clients, cohort, draws = 50, 10, 400
    keys = jax.random.split(jax.random.PRNGKey(7), draws)
    sel = jax.vmap(lambda k: fed.cohort_sample(k, num_clients, cohort))(keys)
    counts = np.bincount(np.asarray(sel).ravel(), minlength=num_clients)
    freq = counts / draws
    p = cohort / num_clients
    sigma = np.sqrt(p * (1 - p) / draws)
    assert abs(freq.mean() - p) < 1e-9          # exactly S picks per draw
    assert np.max(np.abs(freq - p)) < 5 * sigma, (freq.min(), freq.max())


def test_participation_mask_scatters_cohort_sample():
    """The dense mask and the O(S) draw select the SAME clients from the
    same key — the property every dense-vs-cohort equality test rests on."""
    key = jax.random.PRNGKey(5)
    ids = fed.cohort_sample(key, 40, 12)
    mask = fed.participation_mask(key, 40, 12)
    assert float(jnp.sum(mask)) == 12.0
    expect = jnp.zeros((40,)).at[ids].set(1.0)
    assert jnp.array_equal(mask, expect)


# ---------------------------------------------------------------------------
# keyed EF store
# ---------------------------------------------------------------------------


def test_ef_store_gather_scatter_roundtrip():
    store = ef_lib.ef_store_init(20, 4)
    ids = jnp.array([3, 7, 11], jnp.int32)
    rows = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    new = store.scatter(ids, rows)
    assert jnp.array_equal(new.gather(ids), rows)
    # every non-cohort row is bit-frozen (still the zeros it started as)
    others = jnp.array([i for i in range(20) if i not in (3, 7, 11)])
    assert jnp.array_equal(new.gather(others), jnp.zeros((17, 4)))
    # the original store is unchanged (functional update)
    assert float(jnp.sum(jnp.abs(store.data))) == 0.0


def test_ef_store_matches_dense_ef_with_frozen_nonparticipants():
    """Gather/scatter EF round-trip == dense EF: participants' residuals
    identical, non-participants' rows bit-frozen in both layouts."""
    key = jax.random.PRNGKey(9)
    num_clients, dim, cohort = 16, 8, 5
    codec = codecs.make_codec("int8")
    ids = fed.cohort_sample(jax.random.fold_in(key, 1), num_clients, cohort)
    uploads = jax.random.normal(jax.random.fold_in(key, 2), (num_clients, dim))
    ckeys = fed.client_keys(jax.random.fold_in(key, 3),
                            jnp.arange(num_clients))
    pmask = fed.participation_mask(jax.random.fold_in(key, 1), num_clients,
                                   cohort)
    # dense: all clients run the roundtrip, active freezes non-participants
    dense0 = ef_lib.ef_init_stacked(num_clients, dim)
    _, _, dense1 = jax.vmap(
        lambda x, r, k, a: ef_lib.ef_roundtrip(codec, x, r, k, a)
    )(uploads, dense0, ckeys, pmask)
    # keyed: only the cohort's rows are gathered, updated, scattered
    store = ef_lib.ef_store_init(num_clients, dim)
    _, _, rows = jax.vmap(
        lambda x, r, k: ef_lib.ef_roundtrip(codec, x, r, k)
    )(uploads[ids], store.gather(ids), ckeys[ids])
    store1 = store.scatter(ids, rows)
    assert jnp.array_equal(dense1, store1.data)


# ---------------------------------------------------------------------------
# virtual population == materialized dense container
# ---------------------------------------------------------------------------


def test_virtual_data_matches_materialized():
    vd = _virtual(jax.random.PRNGKey(11), 48)
    dense = vd.materialize()
    assert vd.total == int(dense.total)
    ids = jnp.array([0, 17, 47, 3], jnp.int32)
    assert jnp.array_equal(vd.counts_for(ids), dense.counts_for(ids))
    idx = jnp.array([[0, 1, 2], [3, 0, 1], [2, 2, 2], [1, 0, 4]], jnp.int32)
    zv, yv = vd.batch_rows(ids, idx)
    zd, yd = dense.batch_rows(ids, idx)
    assert jnp.array_equal(zv, zd) and jnp.array_equal(yv, yd)
    for a, b in zip(vd.shards_for(ids), dense.shards_for(ids)):
        assert jnp.array_equal(a, b)


def test_virtual_data_total_never_materializes_population():
    """Construction at I = 1e6 must be cheap (chunked total, no (I,) array)
    and materialize() must refuse."""
    vd = _virtual(jax.random.PRNGKey(1), 1_000_000)
    assert vd.total > 0
    with pytest.raises(ValueError, match="materialize"):
        vd.materialize()


def test_virtual_data_ragged_counts():
    vd = _virtual(jax.random.PRNGKey(2), 200, n_min=3, n_max=9)
    counts = np.asarray(vd.counts_for(jnp.arange(200)))
    assert counts.min() >= 3 and counts.max() <= 9
    assert len(np.unique(counts)) > 1          # genuinely ragged


# ---------------------------------------------------------------------------
# single-round equality: cohort_round == sample_round(participation=S)
# ---------------------------------------------------------------------------


def test_cohort_round_matches_sample_round_dense():
    key = jax.random.PRNGKey(21)
    vd = _virtual(jax.random.fold_in(key, 1), 40)
    dense = vd.materialize()
    params = _params(jax.random.fold_in(key, 2))
    rk = jax.random.fold_in(key, 3)
    gd, vd_, ud = fed.sample_round(mlp.per_sample_loss, params, dense, rk, 6,
                                   with_value=True, participation=10)
    gc, vc, uc = fed.cohort_round(mlp.per_sample_loss, params, vd, rk, 6, 10,
                                  with_value=True)
    assert _maxdiff(gd, gc) < 1e-5
    assert abs(float(vd_) - float(vc)) < 1e-5
    sel = jnp.sort(jnp.flatnonzero(ud["participants"]))
    assert jnp.array_equal(sel, jnp.sort(uc["cohort"]))


def test_cohort_round_uploads_scale_with_cohort_only():
    """O(S) invariant: everything the round materializes is (S, ...), never
    (I, ...) — except the EFStore backing, which lives outside the round."""
    vd = _virtual(jax.random.PRNGKey(4), 10_000)
    params = _params(jax.random.PRNGKey(5))
    codec = codecs.make_codec("int8")
    dim = codecs.tree_flat_dim(params)
    store = ef_lib.ef_store_init(10_000, dim)
    g, v, up = fed.cohort_round(mlp.per_sample_loss, params, vd,
                                jax.random.PRNGKey(6), 4, 32,
                                codec=codec, ef=store)
    assert up["cohort"].shape == (32,)
    for leaf in jax.tree.leaves(up["q_grad_sums"]):
        assert leaf.shape[0] == 32
    for leaf in jax.tree.leaves(up["encoded"]):
        assert leaf.shape[0] == 32
    assert up["ef"].data.shape == (10_000, dim)


def test_cohort_round_rejects_dense_ef():
    vd = _virtual(jax.random.PRNGKey(4), 30)
    params = _params(jax.random.PRNGKey(5))
    dense_ef = ef_lib.ef_init_stacked(30, codecs.tree_flat_dim(params))
    with pytest.raises(ValueError, match="EFStore"):
        fed.cohort_round(mlp.per_sample_loss, params, vd,
                         jax.random.PRNGKey(6), 4, 8,
                         codec=codecs.make_codec("int8"), ef=dense_ef)


def test_cohort_drivers_require_participation():
    vd = _virtual(jax.random.PRNGKey(4), 30)
    params = _params(jax.random.PRNGKey(5))
    with pytest.raises(ValueError, match="participation"):
        algorithms.algorithm1(mlp.per_sample_loss, params, vd, _fl(), 2,
                              jax.random.PRNGKey(0), cohort=True)


# ---------------------------------------------------------------------------
# trajectory equality: every sample-based driver, dense engine vs O(S) engine
# ---------------------------------------------------------------------------

I_TRAJ, S_TRAJ, K_TRAJ = 48, 12, 10


def _setup(seed=31):
    key = jax.random.PRNGKey(seed)
    vd = _virtual(jax.random.fold_in(key, 1), I_TRAJ)
    return (vd, vd.materialize(), _params(jax.random.fold_in(key, 2)),
            jax.random.fold_in(key, 3))


def test_trajectory_algorithm1_dense_vs_cohort():
    vd, dense, params0, rk = _setup()
    rd = algorithms.algorithm1(mlp.per_sample_loss, params0, dense, _fl(),
                               K_TRAJ, rk, participation=S_TRAJ)
    rc = algorithms.algorithm1(mlp.per_sample_loss, params0, vd, _fl(),
                               K_TRAJ, rk, participation=S_TRAJ, cohort=True)
    assert _maxdiff(rd.params, rc.params) < 1e-5


def test_trajectory_algorithm1_int8_ef_dense_vs_cohort():
    vd, dense, params0, rk = _setup()
    codec = codecs.make_codec("int8")
    rd = algorithms.algorithm1(mlp.per_sample_loss, params0, dense, _fl(),
                               K_TRAJ, rk, participation=S_TRAJ, codec=codec)
    rc = algorithms.algorithm1(mlp.per_sample_loss, params0, vd, _fl(),
                               K_TRAJ, rk, participation=S_TRAJ, codec=codec,
                               cohort=True)
    assert _maxdiff(rd.params, rc.params) < 1e-5
    # the EF layouts track each other (bit-equality only holds for a single
    # round — see test_ef_store_matches_dense_ef_with_frozen_nonparticipants;
    # over K rounds the engines' iterates differ by float reassociation, so
    # the residuals inherit that tolerance)
    np.testing.assert_allclose(np.asarray(rd.final_state.ef),
                               np.asarray(rc.final_state.ef.data), atol=1e-5)


def test_trajectory_algorithm2_dense_vs_cohort():
    vd, dense, params0, rk = _setup()
    fl = _fl(constrained=True, cost_limit=1.2, penalty_c=1e4)
    codec = codecs.make_codec("int8")
    rd = algorithms.algorithm2(mlp.per_sample_loss, params0, dense, fl,
                               K_TRAJ, rk, participation=S_TRAJ, codec=codec)
    rc = algorithms.algorithm2(mlp.per_sample_loss, params0, vd, fl,
                               K_TRAJ, rk, participation=S_TRAJ, codec=codec,
                               cohort=True)
    assert _maxdiff(rd.params, rc.params) < 1e-5


def test_trajectory_algorithm2_general_dense_vs_cohort():
    vd, dense, params0, rk = _setup()
    fl = _fl(constrained=True, cost_limit=1.2, penalty_c=1e4)
    codec = codecs.make_codec("int8")
    rd = algorithms.algorithm2_general(mlp.per_sample_loss,
                                       mlp.per_sample_loss, params0, dense,
                                       fl, K_TRAJ, rk, participation=S_TRAJ,
                                       codec=codec)
    rc = algorithms.algorithm2_general(mlp.per_sample_loss,
                                       mlp.per_sample_loss, params0, vd,
                                       fl, K_TRAJ, rk, participation=S_TRAJ,
                                       codec=codec, cohort=True)
    assert _maxdiff(rd.params, rc.params) < 1e-5
    for stream in ("obj", "cons"):
        np.testing.assert_allclose(
            np.asarray(rd.final_state.ef[stream]),
            np.asarray(rc.final_state.ef[stream].data), atol=1e-5)


def test_trajectory_sample_sgd_dense_vs_cohort():
    vd, dense, params0, rk = _setup()
    cfg = baselines.SGDConfig(local_steps=2, local_batch=4)
    codec = codecs.make_codec("int8")
    rd = baselines.sample_sgd(mlp.per_sample_loss, params0, dense, cfg,
                              K_TRAJ, rk, participation=S_TRAJ, codec=codec)
    rc = baselines.sample_sgd(mlp.per_sample_loss, params0, vd, cfg,
                              K_TRAJ, rk, participation=S_TRAJ, codec=codec,
                              cohort=True)
    assert _maxdiff(rd.params, rc.params) < 1e-5


def test_trajectory_algorithm1_local_dense_vs_cohort():
    vd, dense, params0, rk = _setup()
    rd = local_updates.algorithm1_local(mlp.per_sample_loss, params0, dense,
                                        _fl(), K_TRAJ, rk, local_steps=2,
                                        participation=S_TRAJ)
    rc = local_updates.algorithm1_local(mlp.per_sample_loss, params0, vd,
                                        _fl(), K_TRAJ, rk, local_steps=2,
                                        participation=S_TRAJ, cohort=True)
    assert _maxdiff(rd.params, rc.params) < 1e-5


def test_trajectory_cohort_sharded_matches_local():
    """The sharded topology splits the COHORT: trajectory equal to the local
    cohort engine (a 1-device mesh still runs shard_map + psum)."""
    vd, _, params0, rk = _setup()
    codec = codecs.make_codec("int8")
    topo = topology_lib.sharded_for(S_TRAJ)
    rl = algorithms.algorithm1(mlp.per_sample_loss, params0, vd, _fl(),
                               K_TRAJ, rk, participation=S_TRAJ, codec=codec,
                               cohort=True)
    rs = algorithms.algorithm1(mlp.per_sample_loss, params0, vd, _fl(),
                               K_TRAJ, rk, participation=S_TRAJ, codec=codec,
                               cohort=True, topology=topo)
    assert _maxdiff(rl.params, rs.params) < 1e-5
    assert jnp.array_equal(rl.final_state.ef.data, rs.final_state.ef.data)
