"""The reference's own copy of the cohort cells' population, written from
its stated random choices so that the reference makes the same rows as the
program without importing it.

Each row is a pure function of (base key, client id, row index): shard
sizes N_i ~ Uniform{n_min..n_max}, label probabilities ~ Dirichlet(alpha),
features = class prototype + Gaussian noise. These are the statistics and
key derivations of the program's ``VirtualFedData``; the benchmark feeds the
program's own population to the timed step and this copy to the reference,
so a row that differs shows as a gap.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


class Population:
    """A fixed federated population of ``num_clients`` ragged, label-skewed
    shards made from ``key``, with the view the reference reads
    (``counts_for``, ``batch_rows``, ``num_clients``, ``total``)."""

    def __init__(self, key, num_clients: int, num_features: int,
                 num_classes: int, n_min: int = 8, n_max: int = 32,
                 noise: float = 4.0, alpha: float = 0.5):
        self.key = key
        self.num_clients = int(num_clients)
        self.n_min, self.n_max = int(n_min), int(n_max)
        self.num_features, self.num_classes = int(num_features), int(num_classes)
        self.noise, self.alpha = float(noise), float(alpha)
        self.protos = (jax.random.normal(
            jax.random.fold_in(self.key, 0x9707), (num_classes, num_features))
            / jnp.sqrt(num_features))
        self.total = int(jnp.sum(self.counts_for(
            jnp.arange(self.num_clients, dtype=jnp.int32))))

    def _count(self, i):
        ck = jax.random.fold_in(self.key, i)
        return (self.n_min + jax.random.randint(
            jax.random.fold_in(ck, 2), (), 0, self.n_max - self.n_min + 1)
        ).astype(jnp.int32)

    def _row(self, ck, log_probs, r):
        kr = jax.random.fold_in(jax.random.fold_in(ck, 3), r)
        label = jax.random.categorical(kr, log_probs)
        z = (self.protos[label] + self.noise * jax.random.normal(
            jax.random.fold_in(kr, 1), (self.num_features,))
            / jnp.sqrt(self.num_features))
        return z, jax.nn.one_hot(label, self.num_classes)

    def _client_rows(self, i, idx):
        ck = jax.random.fold_in(self.key, i)
        lp = jnp.log(jax.random.dirichlet(
            jax.random.fold_in(ck, 1),
            self.alpha * jnp.ones((self.num_classes,))))
        return jax.vmap(lambda r: self._row(ck, lp, r))(idx)

    def counts_for(self, ids):
        return jax.vmap(self._count)(ids)

    def batch_rows(self, ids, idx):
        return jax.vmap(self._client_rows)(ids, idx)
