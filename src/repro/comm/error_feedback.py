"""Error feedback (EF14/EF-SGD style) for compressed q-uploads.

Each client keeps a residual r_i of what its codec dropped so far; before
encoding it adds the residual back:

    target  = q_i + r_i
    enc     = codec.encode(target)          # crosses the wire
    r_i'    = target - decode(enc)          # re-injected next round

For unbiased codecs (stochastic rounding) EF is a harmless variance
reducer; for biased ones (top-k) it is what makes the trajectory track the
dense one — every coordinate's accumulated mass eventually exceeds the
top-k threshold and gets flushed, so as k -> P the compressed trajectory
recovers the dense trajectory exactly (tests/test_comm.py pins k = P).

The residuals are *state*: they ride through the scan-compiled round driver
as part of the carry, wrapped in :class:`CommCarry` next to the optimizer
state (``core/rounds.py::unwrap_comm`` peels the wrapper when extracting
params). Under partial participation a non-selected client neither uploads
nor touches its residual — ``ef_roundtrip(active=...)`` freezes it.

Two layouts exist for the per-client residual matrix:

* the **dense** ``(I, P)`` array (``ef_init_stacked``) — every client's row
  enters the round compute, non-participants frozen via ``active``; the
  bit-level reference for small I;
* the **keyed** :class:`EFStore` (``ef_store_init``) for the O(S) cohort
  engine (DESIGN.md §14) — the same ``(I, P)`` backing lives OUTSIDE the
  per-round compute, in device memory; each round gathers the cohort's
  ``(S, P)`` slice in and scatters the updated slice back. On the TPU both
  are row-copy kernels (``kernels/ef_rows.py``) that touch only the
  cohort's (8, 128) tile groups, at most 8·S·P·4 bytes per access (a row
  moves with its tile group), and the scatter updates the backing in
  place. A non-participant's residual never changes, so the two layouts
  stay bit-equal (pinned in tests/test_cohort.py).

Ordering with DP (DESIGN.md §15): the ``dp=`` clip+noise stage of
core/topology.py runs BEFORE ``ef_roundtrip``, so ``target`` — and hence
the residual the client carries between rounds — is built from the
already-privatized upload. The residual never stores raw (pre-noise)
signal: EF state leaking cannot undo the mechanism, and what EF re-injects
next round is codec error on privatized data, not deferred private signal.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec


class CommCarry(NamedTuple):
    """Scan carry = inner optimizer state + per-client EF residuals."""
    opt: object                    # SSCAState / SGDState / ... (has .params)
    ef: object                     # residual vector(s): (P,), (I, P), or dict


def ef_init(dim: int):
    """Residual for a single P-dim upload stream (e.g. the pjit train loop's
    all-reduced gradient, or the feature-based head upload)."""
    return jnp.zeros((dim,), jnp.float32)


def ef_init_stacked(num_clients: int, dim: int):
    """Per-client residuals for sample-based rounds: one (P,) vector each."""
    return jnp.zeros((num_clients, dim), jnp.float32)


class EFStore(NamedTuple):
    """Keyed per-client residual store for the cohort engine: the (I, P)
    backing stays out of the round's (S, ...) compute; rounds touch only the
    cohort's rows via :meth:`gather` / :meth:`scatter`.

    Where the round is compiled for a TPU, both are row-copy kernels
    (``kernels/ef_rows.py``) that move the cohort's (8, 128) tile groups
    and no other byte of the backing; the scatter writes the backing in
    place through the kernel's input/output alias. A NamedTuple is a
    registered pytree, so the store rides the scan carry (inside
    :class:`CommCarry`) and XLA keeps one (I, P) buffer across the scanned
    rounds. Elsewhere (CPU) they are ``jnp.take`` / ``.at[ids].set``, the
    reference the kernels are tested against.

    ``ids`` must be distinct and in [0, I), as ``fed.cohort_sample``'s
    draw without replacement is: the kernels rely on it.
    """
    data: jnp.ndarray              # (I, P) residual backing

    @property
    def num_clients(self):
        return self.data.shape[0]

    @property
    def dim(self):
        return self.data.shape[1]

    def gather(self, ids, mesh=None):
        """(S,) client ids -> (S, P) residual rows for this round's cohort.
        ``mesh``: the devices the round runs on, where there are several
        (``ShardedTopology.mesh``); the store is replicated over them."""
        from repro.kernels.ef_rows import ef_rows_gather   # see _rows_op
        return _rows_op(ef_rows_gather, _take_rows, mesh, self.data, ids)

    def scatter(self, ids, rows, mesh=None):
        """Write the cohort's updated rows back; every other client's
        residual keeps its bits."""
        from repro.kernels.ef_rows import ef_rows_scatter  # see _rows_op
        return self._replace(data=_rows_op(
            ef_rows_scatter, _set_rows, mesh, self.data, ids, rows))


def _take_rows(data, ids):
    return jnp.take(data, ids, axis=0)


def _set_rows(data, ids, rows):
    return data.at[ids].set(rows)


def _rows_op(kernel, reference, mesh, *args):
    """``kernel(*args)`` where the program is compiled for a TPU, else
    ``reference(*args)``. A Mosaic kernel is not partitioned by XLA, so on a
    mesh of several devices it runs on each device's replica of the store.
    The kernels are imported by the methods that use them: Pallas takes
    1-2 s to import, which a program without a store need not pay."""
    if mesh is not None and mesh.size > 1:
        kernel = jax.shard_map(kernel, mesh=mesh, in_specs=PartitionSpec(),
                               out_specs=PartitionSpec(), check_vma=False)
    return jax.lax.platform_dependent(*args, tpu=kernel, default=reference)


def ef_store_init(num_clients: int, dim: int) -> EFStore:
    """Zero-initialized keyed residual store for `fed.cohort_round`, its
    (I, P) backing in device memory."""
    return EFStore(data=jnp.zeros((num_clients, dim), jnp.float32))


def with_comm_carry(codec, body):
    """Wrap a round body into a (state, inp) scan step with the EF carry
    handled in ONE place (every driver shares this, so no copy can forget
    the residual rewrap). ``body(state, inp, ef) -> (new_state, new_ef,
    metrics)`` receives ef=None when no codec is configured; with a codec
    the step's state is CommCarry(opt=state, ef=residuals)."""
    def step(state, inp):
        if codec is None:
            new, _, metrics = body(state, inp, None)
            return new, metrics
        new, new_ef, metrics = body(state.opt, inp, state.ef)
        return CommCarry(opt=new, ef=new_ef), metrics

    return step


def ef_roundtrip(codec, x, residual, key=None, active=None):
    """One error-feedback compression step on a flat upload vector.

    Returns (enc, x_hat, new_residual). ``active`` (0/1 scalar, typically a
    participation-mask entry under vmap) freezes the residual of a client
    that did not upload this round; its x_hat is zero-masked server-side by
    the aggregation weights, so only the residual needs guarding.

    Conservation invariant (any codec): x_hat + new_residual == x + residual.
    """
    target = x + residual
    enc, x_hat = codec.roundtrip(target, key)
    new_residual = target - x_hat
    if active is not None:
        new_residual = jnp.where(active > 0, new_residual, residual)
    return enc, x_hat, new_residual
