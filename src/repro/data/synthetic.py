"""Synthetic datasets (the container is offline; MNIST is unavailable).

`classification_dataset` mirrors MNIST's dimensions (N=60000, P=784, L=10) as
class-conditional Gaussians over random class prototypes — a nonconvex-loss
classification task of the same shape, so all the paper's *relative* claims
(convergence ordering, comm/comp tradeoffs, constrained feasibility) can be
validated. Deterministic given the seed.

`token_dataset` produces LM token streams (Zipf-ish marginals with a Markov
bigram structure) for the model-zoo training examples.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def classification_dataset(key, n: int = 60_000, num_features: int = 784,
                           num_classes: int = 10, noise: float = 1.0,
                           test_n: int = 10_000):
    kp, kl, kn, klt, knt = jax.random.split(key, 5)
    protos = jax.random.normal(kp, (num_classes, num_features)) / jnp.sqrt(num_features)

    def make(klab, knoise, count):
        labels = jax.random.randint(klab, (count,), 0, num_classes)
        z = protos[labels] + noise * jax.random.normal(
            knoise, (count, num_features)) / jnp.sqrt(num_features)
        y = jax.nn.one_hot(labels, num_classes)
        return z, y, labels

    train = make(kl, kn, n)
    test = make(klt, knt, test_n)
    return train, test


def federated_classification_dataset(key, num_clients: int, n: int = 60_000,
                                     num_features: int = 784,
                                     num_classes: int = 10, noise: float = 1.0,
                                     test_n: int = 10_000,
                                     dirichlet_alpha: float = None):
    """classification_dataset pre-partitioned into client shards.

    dirichlet_alpha=None gives the seed's IID equal shards; a float α draws
    the standard Dirichlet(α) label-skew partition (fed.partition_dirichlet),
    producing ragged non-IID N_i — the statistical-heterogeneity regime the
    paper's Theorems 1-4 cover (N_i varies).

    Returns (SampleFedData, (z_train, y_train, labels), (z_test, y_test,
    labels_test)).
    """
    from repro.core import fed

    train, test = classification_dataset(key, n=n, num_features=num_features,
                                         num_classes=num_classes, noise=noise,
                                         test_n=test_n)
    z, y, _ = train
    pkey = jax.random.fold_in(key, 0xfed)
    if dirichlet_alpha is None:
        data = fed.partition_samples(z, y, num_clients, key=pkey)
    else:
        data = fed.partition_dirichlet(z, y, num_clients, pkey,
                                       alpha=dirichlet_alpha)
    return data, train, test


class VirtualFedData:
    """Virtual federated population: client shards DERIVED on the fly from
    (base key, client id) instead of stored — so ``--clients 1000000`` never
    materializes a dataset (DESIGN.md §14).

    Statistics match `federated_classification_dataset`'s heterogeneity
    regime: class-conditional Gaussians over shared prototypes, per-client
    label skew probs ~ Dirichlet(α·1_L), ragged shard sizes
    N_i ~ Uniform{n_min..n_max}. Every row is a pure deterministic function
    of (key, client id, row index), so

    * the O(S) cohort engine can ask for exactly the cohort's rows
      (`counts_for`/`batch_rows`/`shards_for` — the same three-method data
      view `core.fed.SampleFedData` implements by gathering), touching O(S)
      state per round, and
    * `materialize()` produces the bit-identical dense `SampleFedData`
      (same row values, same zero padding) for small populations — the
      equality reference tests/test_cohort.py and benchmarks/scale_bench.py
      pin the cohort engine against.

    ``total`` (the population sample count N in eq. 9's weights) is reduced
    once at construction in fixed-size id chunks — no (I,)-shaped array is
    ever built, keeping construction O(I/chunk) dispatches and O(chunk)
    memory even at I = 1e6.
    """

    def __init__(self, key, num_clients: int, n_min: int = 8,
                 n_max: int = 32, num_features: int = 16,
                 num_classes: int = 4, noise: float = 1.0,
                 alpha: float = 0.5):
        if n_min < 1 or n_max < n_min:
            raise ValueError(f"need 1 <= n_min <= n_max, got [{n_min}, {n_max}]")
        self.key = key
        self.num_clients = int(num_clients)
        self.n_min, self.n_max = int(n_min), int(n_max)
        self.num_features, self.num_classes = int(num_features), int(num_classes)
        self.noise, self.alpha = float(noise), float(alpha)
        self.protos = (jax.random.normal(
            jax.random.fold_in(key, 0x9707), (num_classes, num_features))
            / jnp.sqrt(num_features))
        self.total = int(self._population_total())

    # -- per-client generators (each a pure function of the client id) -----

    def _client_key(self, i):
        return jax.random.fold_in(self.key, i)

    def _count(self, i):
        """True N_i ~ Uniform{n_min..n_max}, keyed by client id."""
        ck = self._client_key(i)
        return (self.n_min + jax.random.randint(
            jax.random.fold_in(ck, 2), (), 0, self.n_max - self.n_min + 1)
        ).astype(jnp.int32)

    def _log_probs(self, ck):
        """Client label-skew: log p ~ log Dirichlet(α·1_L)."""
        probs = jax.random.dirichlet(
            jax.random.fold_in(ck, 1),
            self.alpha * jnp.ones((self.num_classes,)))
        return jnp.log(probs)

    def _row(self, ck, log_probs, r):
        """Row r of a client's shard: label ~ Categorical(p_client), feature
        = prototype + Gaussian noise. Purely (client key, row index)-keyed,
        so cohort gathers and dense materialization agree bitwise."""
        kr = jax.random.fold_in(jax.random.fold_in(ck, 3), r)
        label = jax.random.categorical(kr, log_probs)
        z = (self.protos[label] + self.noise * jax.random.normal(
            jax.random.fold_in(kr, 1), (self.num_features,))
            / jnp.sqrt(self.num_features))
        return z, jax.nn.one_hot(label, self.num_classes)

    def _client_rows(self, i, idx):
        ck = self._client_key(i)
        lp = self._log_probs(ck)
        return jax.vmap(lambda r: self._row(ck, lp, r))(idx)

    def _population_total(self):
        """Σ_i N_i reduced in 4096-id chunks — never an (I,) array."""
        chunk = 4096
        num_chunks = -(-self.num_clients // chunk)

        def body(c, acc):
            ids = c * chunk + jnp.arange(chunk, dtype=jnp.int32)
            counts = jax.vmap(self._count)(ids)
            return acc + jnp.sum(
                jnp.where(ids < self.num_clients, counts, 0))

        return jax.lax.fori_loop(0, num_chunks, body, jnp.zeros((), jnp.int32))

    # -- the cohort data view (same contract as SampleFedData) -------------

    def counts_for(self, ids):
        """(S,) true N_i for the given client ids."""
        return jax.vmap(self._count)(ids)

    def batch_rows(self, ids, idx):
        """(S,) ids + (S, B) row indices -> ((S, B, P), (S, B, L)), each row
        generated directly — bitwise what `materialize()` would store."""
        return jax.vmap(self._client_rows)(ids, idx)

    def shards_for(self, ids):
        """Full padded shards for the cohort: rows r >= N_i are zero, exactly
        matching the dense container's padding convention."""
        counts = self.counts_for(ids)
        rows = jnp.arange(self.n_max, dtype=jnp.int32)
        feats, labs = jax.vmap(
            lambda i: self._client_rows(i, rows))(ids)
        valid = (rows[None, :] < counts[:, None])
        return (feats * valid[:, :, None], labs * valid[:, :, None], counts)

    def materialize(self, max_scalars: int = 50_000_000):
        """Dense `SampleFedData` with identical row values and padding — the
        small-I equality reference. Refuses population sizes whose dense
        form would not fit (that regime is the whole point of this class)."""
        from repro.core import fed

        scalars = (self.num_clients * self.n_max
                   * (self.num_features + self.num_classes))
        if scalars > max_scalars:
            raise ValueError(
                f"materialize() would build ~{scalars:.2e} scalars for "
                f"I={self.num_clients} — the virtual view exists so this "
                "never happens; use the cohort engine instead")
        ids = jnp.arange(self.num_clients, dtype=jnp.int32)
        feats, labs, counts = self.shards_for(ids)
        return fed.SampleFedData(feats, labs, counts)


class VirtualTokenData:
    """Virtual cross-silo LM population: silo i holds N_i packed sequences of
    ``seq_len`` tokens, each row a pure function of (key, silo id, row), so
    the cohort engine reads it exactly as it reads `VirtualFedData`
    (``counts_for``, ``batch_rows``, ``num_clients``, ``total``).

    * N_i is heavy-tailed: floor(n_min / (1 - u)^(1/tail)) capped at n_max,
      u ~ Uniform[0, 1) keyed by silo id (tail 1: a Pareto tail, so a few
      silos hold most of the sequences).
    * Tokens follow a silo's own Markov chain over the vocabulary: silo i
      draws a (vocab, fanout) successor table from its key; row r starts at
      a uniform token and takes one of the current token's ``fanout``
      successors uniformly at each step. Documents are concatenated with no
      mask between them (a packed row is one causal sequence).

    ``batch_rows`` returns (tokens, targets), each (S, B, seq_len): targets
    are the tokens shifted by one.
    """

    def __init__(self, key, num_clients: int, seq_len: int, vocab_size: int,
                 n_min: int = 64, n_max: int = 1024, tail: float = 1.0,
                 fanout: int = 4):
        if n_min < 1 or n_max < n_min:
            raise ValueError(f"need 1 <= n_min <= n_max, got [{n_min}, {n_max}]")
        self.key = key
        self.num_clients = int(num_clients)
        self.seq_len, self.vocab_size = int(seq_len), int(vocab_size)
        self.n_min, self.n_max = int(n_min), int(n_max)
        self.tail, self.fanout = float(tail), int(fanout)
        self.total = int(jnp.sum(self.counts_for(
            jnp.arange(self.num_clients, dtype=jnp.int32))))

    def _count(self, i):
        u = jax.random.uniform(jax.random.fold_in(
            jax.random.fold_in(self.key, i), 2))
        n = jnp.floor(self.n_min / (1.0 - u) ** (1.0 / self.tail))
        return jnp.minimum(n, self.n_max).astype(jnp.int32)

    def _row(self, ck, succ, r):
        kr = jax.random.fold_in(jax.random.fold_in(ck, 3), r)
        start = jax.random.randint(jax.random.fold_in(kr, 0), (), 0,
                                   self.vocab_size)
        picks = jax.random.randint(jax.random.fold_in(kr, 1),
                                   (self.seq_len,), 0, self.fanout)

        def step(tok, c):
            nxt = succ[tok, c]
            return nxt, nxt

        _, rest = jax.lax.scan(step, start, picks)
        seq = jnp.concatenate([start[None], rest])
        return seq[:-1], seq[1:]

    def _client_rows(self, i, idx):
        ck = jax.random.fold_in(self.key, i)
        succ = jax.random.randint(jax.random.fold_in(ck, 1),
                                  (self.vocab_size, self.fanout), 0,
                                  self.vocab_size)
        return jax.vmap(lambda r: self._row(ck, succ, r))(idx)

    def counts_for(self, ids):
        """(S,) true N_i for the given silo ids."""
        return jax.vmap(self._count)(ids)

    def batch_rows(self, ids, idx):
        """(S,) ids + (S, B) row indices -> (tokens, targets), (S, B, T)."""
        return jax.vmap(self._client_rows)(ids, idx)


def token_dataset(key, vocab_size: int, n_tokens: int, order: int = 1):
    """Markov bigram stream: next-token depends on current via a random sparse
    transition; gives a learnable LM signal with nonzero optimal loss."""
    kt, ks = jax.random.split(key)
    fanout = 4
    nexts = jax.random.randint(kt, (vocab_size, fanout), 0, vocab_size)

    def step(tok, k):
        choice = jax.random.randint(k, (), 0, fanout)
        nxt = nexts[tok, choice]
        return nxt, nxt

    _, toks = jax.lax.scan(step, jnp.zeros((), jnp.int32),
                           jax.random.split(ks, n_tokens))
    return toks


def sample_window(tokens, key, batch: int, seq: int):
    """One {tokens, targets} batch of random (seq+1)-token windows. Pure and
    traceable — the scan-compiled train driver calls it inside jit."""
    n = tokens.shape[0] - seq - 1
    starts = jax.random.randint(key, (batch,), 0, n)
    idx = starts[:, None] + jnp.arange(seq + 1)[None, :]
    window = tokens[idx]
    return {"tokens": window[:, :-1], "targets": window[:, 1:]}


def make_batch_iterator(tokens, batch: int, seq: int, key):
    """Infinite iterator of {tokens, targets} windows."""
    while True:
        key, sub = jax.random.split(key)
        yield sample_window(tokens, sub, batch, seq)
