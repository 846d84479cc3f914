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
  per-round compute (device-resident by default, host-offloadable behind
  the same interface); each round gathers the cohort's ``(S, P)`` slice in
  and scatters the updated slice back, O(S·P) touched per round. A
  non-participant's row is never read or written, so the two layouts stay
  bit-equal (pinned in tests/test_cohort.py).

Ordering with DP (DESIGN.md §15): the ``dp=`` clip+noise stage of
core/topology.py runs BEFORE ``ef_roundtrip``, so ``target`` — and hence
the residual the client carries between rounds — is built from the
already-privatized upload. The residual never stores raw (pre-noise)
signal: EF state leaking cannot undo the mechanism, and what EF re-injects
next round is codec error on privatized data, not deferred private signal.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp


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

    A NamedTuple is a registered pytree, so the store rides the scan carry
    (inside :class:`CommCarry`) unchanged — and because the scatter is the
    carry's only use of the backing, XLA donates/aliases the buffer across
    scan iterations: the update is in-place, not an (I, P) copy per round.
    """
    data: jnp.ndarray              # (I, P) residual backing

    @property
    def num_clients(self):
        return self.data.shape[0]

    @property
    def dim(self):
        return self.data.shape[1]

    def gather(self, ids):
        """(S,) client ids -> (S, P) residual rows for this round's cohort."""
        return jnp.take(self.data, ids, axis=0)

    def scatter(self, ids, rows):
        """Write the cohort's updated rows back; every other client's
        residual is bit-untouched (never read, never written)."""
        return self._replace(data=self.data.at[ids].set(rows))


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
