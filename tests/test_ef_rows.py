"""EF store row kernels (kernels/ef_rows.py, DESIGN.md §14) in Pallas
interpret mode: bit-equal to the jnp reference (`jnp.take` for the gather,
`.at[ids].set` for the scatter), no other row of the store touched, and a
gather after a scatter returns the written rows. Plus: `EFStore` takes the
reference path where the round is not compiled for a TPU.

The kernels move (8, 128) tile groups; the cases cover I and S that are
not multiples of 8 (the last tile group is partial), P that is not a
multiple of 128, ids 0 and I - 1, ids that share a tile group (adjacent,
so the scatter merges them, and not), and P split into column chunks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.comm import error_feedback as ef_lib
from repro.kernels import ef_rows

# (I, P, S): P % 128 == 0 and not; I, S % 8 == 0 and not
CASES = [(37, 256, 12), (37, 200, 16), (5, 130, 3), (64, 384, 40),
         (21, 128, 21), (300, 1000, 64)]


def _ids(num_clients, cohort, seed=0):
    """Distinct ids that start with 0 and end with I - 1; where I allows,
    8 and 9 (one tile group, adjacent: the scatter merges them) and 16 and
    17 (one tile group, another id between them)."""
    rng = np.random.default_rng(seed)
    head = [0, 8, 9, 16, 3, 17] if num_clients > 17 else [0]
    rest = [int(i) for i in rng.permutation(num_clients)
            if i not in head and i != num_clients - 1]
    return jnp.asarray((head + rest)[:cohort - 1] + [num_clients - 1],
                       jnp.int32)


def _store(num_clients, dim, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (num_clients, dim))


@pytest.mark.parametrize("num_clients,dim,cohort", CASES)
def test_gather_bit_equal_to_take(num_clients, dim, cohort):
    store, ids = _store(num_clients, dim), _ids(num_clients, cohort)
    got = ef_rows.ef_rows_gather(store, ids, interpret=True)
    assert got.shape == (cohort, dim)
    assert jnp.array_equal(got, jnp.take(store, ids, axis=0))


@pytest.mark.parametrize("num_clients,dim,cohort", CASES)
def test_scatter_bit_equal_and_leaves_other_rows(num_clients, dim, cohort):
    store, ids = _store(num_clients, dim), _ids(num_clients, cohort)
    rows = _store(cohort, dim, seed=2)
    got = ef_rows.ef_rows_scatter(store, ids, rows, interpret=True)
    assert got.shape == store.shape
    assert jnp.array_equal(got, store.at[ids].set(rows))
    others = np.setdiff1d(np.arange(num_clients), np.asarray(ids))
    assert jnp.array_equal(got[others], store[others])
    # a gather after the scatter returns the written rows
    assert jnp.array_equal(
        ef_rows.ef_rows_gather(got, ids, interpret=True), rows)


@pytest.mark.parametrize("dim", [1000, 384])
def test_column_chunks(monkeypatch, dim):
    """P over several column chunks, the last one shifted left to end at
    the padded width (1000 -> 1024: four chunks of 256; 384: two chunks
    of 256 that overlap on 128 columns)."""
    monkeypatch.setattr(ef_rows, "BLOCK_BYTES", 2 * 4 * 128 * 16)
    assert ef_rows._chunking(12, dim) == ({1000: 4, 384: 2}[dim], 256)
    store, ids = _store(37, dim), _ids(37, 12)
    rows = _store(12, dim, seed=2)
    assert jnp.array_equal(ef_rows.ef_rows_gather(store, ids, interpret=True),
                           store[ids])
    assert jnp.array_equal(
        ef_rows.ef_rows_scatter(store, ids, rows, interpret=True),
        store.at[ids].set(rows))


@pytest.mark.parametrize("num_clients,dim,cohort",
                         [(37, 200, 12), (64, 128, 40), (300, 256, 64)])
def test_tpu_interpreter_finds_no_race(num_clients, dim, cohort):
    """The TPU interpreter, with its race detector on, runs both kernels
    with DMA semaphores and waits as the chip would; each DMA moves its
    bytes only when it is waited on, so a buffer reused before its write
    has landed shows as a wrong result."""
    params = pltpu.InterpretParams(detect_races=True)
    store, ids = _store(num_clients, dim), _ids(num_clients, cohort)
    rows = _store(cohort, dim, seed=2)
    assert jnp.array_equal(
        ef_rows.ef_rows_gather(store, ids, interpret=params), store[ids])
    assert jnp.array_equal(
        ef_rows.ef_rows_scatter(store, ids, rows, interpret=params),
        store.at[ids].set(rows))
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    assert not interpret_pallas_call.races.races_found


def test_ef_store_takes_reference_path_off_tpu():
    """Compiled for this (non-TPU) backend, `EFStore` lowers to XLA's own
    gather and scatter, with no kernel, and gives the reference's bits."""
    assert jax.default_backend() != "tpu"
    store = ef_lib.EFStore(data=_store(37, 200))
    ids = _ids(37, 12)
    rows = _store(12, 200, seed=2)
    for fn, args in ((lambda s, i: s.gather(i), (store, ids)),
                     (lambda s, i, r: s.scatter(i, r).data,
                      (store, ids, rows))):
        hlo = jax.jit(fn).lower(*args).as_text()
        assert "tpu_custom_call" not in hlo and "ef_rows" not in hlo
    assert jnp.array_equal(store.gather(ids), store.data[ids])
    assert jnp.array_equal(store.scatter(ids, rows).data,
                           store.data.at[ids].set(rows))
