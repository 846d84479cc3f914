"""Federated protocol layer: client data containers, per-round uploads
(q-statistics), aggregation with N_i/(BN) weights, and communication-load
accounting (Fig. 3's x/y axes).

The privacy mechanism of the paper is *model aggregation*: only B-summed
statistics (q vectors) ever leave a client. The round functions below return
an `uploads` structure so tests can assert exactly what crossed the boundary.

Both round functions take an optional ``codec=`` (repro.comm.codecs): each
client's flat q-upload is then lossily compressed (with per-client error
feedback when an ``ef`` residual is threaded in) before the server decodes
and aggregates — what crosses the boundary is the codec's wire format, and
``uploads`` exposes it plus the updated residuals and the exact wire bytes
(repro.comm.accounting). Byte-level Fig.-3 bookkeeping lives in
``repro.comm.accounting``; the float counters are re-exported below.

``sample_round`` additionally takes ``topology=`` (repro.core.topology,
DESIGN.md §11), selecting whether its clients run under a single-device vmap
or device-sharded over the mesh via shard_map with the aggregation as a
weighted psum — same math, same uploads surface, same wire bytes.

``cohort_round`` is the participant-only realization of the same protocol
(DESIGN.md §14): instead of computing every client and zero-masking the
non-participants server-side, it draws the S-client cohort in O(S) work
(``cohort_sample``, a keyed Feistel permutation over the virtual population
— no length-I permutation, no dense mask), gathers only the cohort's data
and error-feedback residuals, and runs client compute / codec encode / the
weighted aggregation over the (S, ...) cohort axis. Per-round compute and
carried state scale with S, not I; the unbiased I/S Horvitz-Thompson
reweighting of eq. (9) is preserved, and at small I the trajectory matches
``sample_round`` on the same keys (atol 1e-5 — reassociation only).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.comm import accounting as comm_accounting
from repro.comm import codecs as comm_codecs
from repro.core import topology as topology_lib
from repro.obs import trace as obs_trace


class SampleFedData(NamedTuple):
    """Sample-based (horizontal) FL: client i holds rows N_i. Ragged client
    datasets are stored padded to max N_i; `counts` carries the true N_i."""
    features: jnp.ndarray     # (I, N_max, P)
    labels: jnp.ndarray       # (I, N_max, L) one-hot
    counts: jnp.ndarray       # (I,) true N_i

    @property
    def num_clients(self):
        return self.features.shape[0]

    @property
    def total(self):
        return jnp.sum(self.counts)

    # -- cohort-engine data view (DESIGN.md §14) ---------------------------
    # The O(S) cohort engine never touches the population axis: it asks the
    # data container for exactly the cohort's slice. A virtual population
    # (data/synthetic.VirtualFedData) implements the same three methods by
    # GENERATING the slice from (base key, client id) instead of gathering.

    def counts_for(self, ids):
        """(S,) true N_i for the given client ids."""
        return jnp.take(self.counts, ids, axis=0)

    def batch_rows(self, ids, idx):
        """Cohort mini-batches: (S,) ids + (S, B) in-shard row indices ->
        ((S, B, P) features, (S, B, L) labels). Row values are identical to
        ``take(features[i], idx_i)`` on the dense shard."""
        return (self.features[ids[:, None], idx],
                self.labels[ids[:, None], idx])

    def shards_for(self, ids):
        """Full padded shards for the cohort: ((S, N_max, P), (S, N_max, L),
        (S,) counts) — for drivers whose clients loop over local batches
        (baselines.sample_sgd, local_updates)."""
        return (jnp.take(self.features, ids, axis=0),
                jnp.take(self.labels, ids, axis=0),
                jnp.take(self.counts, ids, axis=0))


class FeatureFedData(NamedTuple):
    """Feature-based (vertical) FL: client i holds feature block P_i (equal
    sizes; pad features if needed) and the shared labels."""
    feature_blocks: jnp.ndarray   # (I, N, P_i)
    labels: jnp.ndarray           # (N, L)

    @property
    def num_clients(self):
        return self.feature_blocks.shape[0]

    @property
    def total(self):
        return self.feature_blocks.shape[1]


def partition_samples(features, labels, num_clients, key=None) -> SampleFedData:
    """Split N samples into I (near-)equal client shards."""
    n = features.shape[0]
    if key is not None:
        perm = jax.random.permutation(key, n)
        features, labels = features[perm], labels[perm]
    per = n // num_clients
    features = features[: per * num_clients].reshape(num_clients, per, -1)
    labels = labels[: per * num_clients].reshape(num_clients, per, -1)
    counts = jnp.full((num_clients,), per, jnp.int32)
    return SampleFedData(features, labels, counts)


def partition_ragged(feature_shards, label_shards) -> SampleFedData:
    """Build a padded SampleFedData from explicit per-client shards (lists of
    (N_i, P) / (N_i, L) arrays with heterogeneous N_i). Padding rows are zero
    and never selected: `sample_batches` draws indices in [0, N_i)."""
    import numpy as np

    counts = np.asarray([len(f) for f in feature_shards], np.int32)
    if (counts <= 0).any():
        raise ValueError(f"every client needs >= 1 sample, got counts={counts}")
    n_max = int(counts.max())
    p = np.asarray(feature_shards[0]).shape[-1]
    l = np.asarray(label_shards[0]).shape[-1]
    feats = np.zeros((len(counts), n_max, p), np.asarray(feature_shards[0]).dtype)
    labs = np.zeros((len(counts), n_max, l), np.asarray(label_shards[0]).dtype)
    for i, (f, y) in enumerate(zip(feature_shards, label_shards)):
        feats[i, : counts[i]] = np.asarray(f)
        labs[i, : counts[i]] = np.asarray(y)
    return SampleFedData(jnp.asarray(feats), jnp.asarray(labs),
                         jnp.asarray(counts))


def partition_dirichlet(features, labels, num_clients, key,
                        alpha: float = 0.5) -> SampleFedData:
    """Non-IID label-skew partition: for each class c, client shares of the
    class-c samples are drawn ~ Dirichlet(alpha·1_I), the standard statistical-
    heterogeneity benchmark protocol. Every sample is assigned to exactly one
    client; N_i become genuinely ragged. alpha → ∞ recovers IID; alpha → 0
    gives near single-class clients. A client that ends up empty is given one
    sample from the largest client (N_i >= 1 is a protocol invariant)."""
    import numpy as np

    lab_int = np.asarray(jnp.argmax(labels, axis=-1))
    features, labels = np.asarray(features), np.asarray(labels)
    num_classes = labels.shape[-1]
    shards = [[] for _ in range(num_clients)]
    for c in range(num_classes):
        idx = np.flatnonzero(lab_int == c)
        if idx.size == 0:
            continue
        kc = jax.random.fold_in(key, c)
        idx = idx[np.asarray(jax.random.permutation(kc, idx.size))]
        props = np.asarray(jax.random.dirichlet(
            jax.random.fold_in(kc, 1), alpha * jnp.ones((num_clients,))))
        # largest-remainder rounding so the splits sum exactly to idx.size
        raw = props * idx.size
        take = np.floor(raw).astype(int)
        rem = idx.size - take.sum()
        take[np.argsort(raw - np.floor(raw))[::-1][:rem]] += 1
        for i, chunk in enumerate(np.split(idx, np.cumsum(take)[:-1])):
            shards[i].extend(chunk.tolist())
    for i in range(num_clients):            # enforce N_i >= 1
        if not shards[i]:
            donor = max(range(num_clients), key=lambda j: len(shards[j]))
            shards[i].append(shards[donor].pop())
    return partition_ragged([features[s] for s in shards],
                            [labels[s] for s in shards])


def partition_features(features, labels, num_clients) -> FeatureFedData:
    """Split the P feature columns into I equal blocks (pad with zero cols)."""
    n, p = features.shape
    per = -(-p // num_clients)   # ceil
    pad = per * num_clients - p
    if pad:
        features = jnp.pad(features, ((0, 0), (0, pad)))
    blocks = features.reshape(n, num_clients, per).transpose(1, 0, 2)
    return FeatureFedData(blocks, labels)


# ---------------------------------------------------------------------------
# shared codec-argument validation — sample_round and feature_round fail
# identically (same messages, same conditions); tests/test_feature_topology.py
# pins the parity
# ---------------------------------------------------------------------------


def _check_codec_args(round_name: str, codec, ef):
    """Reject EF residuals without a codec in BOTH round functions (silently
    ignoring them would drop the caller's error-feedback state)."""
    if codec is None and ef is not None:
        raise ValueError(
            f"{round_name}: error-feedback residuals (ef=) were passed "
            "without codec= — EF is only meaningful for a lossy codec; "
            "pass codec= or drop ef=")


def _check_ef_shape(round_name: str, stream: str, residual, expected_shape):
    """Shape-check one EF residual stream against the upload it feeds, with
    the same message format for both round functions."""
    if residual is None:
        return
    if not hasattr(residual, "shape") or tuple(residual.shape) != tuple(
            expected_shape):
        got = tuple(residual.shape) if hasattr(residual, "shape") else type(
            residual).__name__
        raise ValueError(
            f"{round_name}: error-feedback residuals for stream "
            f"'{stream}' have shape {got}, expected {tuple(expected_shape)} "
            "— rebuild the residual state with the matching "
            "repro.comm.error_feedback ef_init helper")


# ---------------------------------------------------------------------------
# O(S) cohort sampling: keyed Feistel permutation over the virtual population
# ---------------------------------------------------------------------------


def _feistel_mix(x):
    """murmur3 finalizer on uint32 — the Feistel round function's hash."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> jnp.uint32(16))


def _feistel(x, round_keys, hi_bits: int, lo_bits: int):
    """Alternating (unbalanced) keyed Feistel network: a bijection on
    [0, 2^(hi_bits+lo_bits)) for ANY round function — each round modularly
    adds a hash of one half to the other, which is invertible regardless of
    the hash. The unbalanced split lets the domain be 2^ceil(log2 I) rather
    than the next even power of two, so cycle-walking rejects < 50% of
    values at every population size (a balanced network's domain can
    overshoot I by almost 4x, tripling the expected walk length)."""
    lo_mask = jnp.uint32((1 << lo_bits) - 1)
    hi_mask = jnp.uint32((1 << hi_bits) - 1)
    hi, lo = x >> lo_bits, x & lo_mask
    for r in range(round_keys.shape[0]):
        if r % 2 == 0:
            lo = (lo + _feistel_mix(hi ^ round_keys[r])) & lo_mask
        else:
            hi = (hi + _feistel_mix(lo ^ round_keys[r])) & hi_mask
    return (hi << lo_bits) | lo


_FEISTEL_ROUNDS = 6
_FEISTEL_MIN_BITS = 8         # >= 8-bit domain: better mixing for tiny I


def cohort_sample(key, num_clients: int, cohort: int):
    """Draw S = `cohort` client ids uniformly without replacement from a
    population of `num_clients` in O(S) work — no length-I permutation.

    The keyed Feistel permutation π is a bijection on the power-of-two
    domain 2^ceil(log2 I) >= I; the cohort is {walk(π(0)), ..., walk(π(S-1))}
    where `walk` cycle-walks π until the value lands inside [0, I) (expected
    < 2 steps: the domain is < 2·I). A fresh key gives an independent
    pseudorandom permutation, so each client appears in the cohort w.p.
    exactly S/I (pinned statistically in tests/test_cohort.py). This is what
    lets the participation draw — and everything keyed off it — scale with
    the cohort instead of the population (DESIGN.md §14).
    """
    if not 1 <= cohort <= num_clients:
        raise ValueError(f"cohort must be in [1, {num_clients}], got {cohort}")
    bits = max(_FEISTEL_MIN_BITS, max(num_clients - 1, 1).bit_length())
    lo_bits, hi_bits = bits // 2, bits - bits // 2
    round_keys = jax.random.bits(key, (_FEISTEL_ROUNDS,), jnp.uint32)
    n = jnp.uint32(num_clients)

    def perm(x):
        return _feistel(x, round_keys, hi_bits, lo_bits)

    def one(i):
        return jax.lax.while_loop(lambda x: x >= n, perm, perm(i))

    ids = jax.vmap(one)(jnp.arange(cohort, dtype=jnp.uint32))
    return ids.astype(jnp.int32)


def client_keys(key, ids):
    """Per-client PRNG keys keyed by STABLE client id (fold_in, not split):
    the dense engine (ids = arange(I)) and the cohort engine (ids = the S
    drawn ids) derive the identical key for the same client, which is what
    makes their trajectories comparable round for round."""
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(ids)


# ---------------------------------------------------------------------------
# sample-based rounds (Algorithm 1/2 steps 3-4)
# ---------------------------------------------------------------------------


def _client_upload(per_sample_loss, params, zb, yb, mask):
    """A client's (q, Σ f, stats) over its masked batch: q = Σ_n ∇f(ω; x_n).
    ``per_sample_loss`` returns the per-row losses, or (per-row losses,
    stats) where the model counts work of its own (a language model's
    expert slots); stats come back per client, {} where there are none."""
    def batch_sum_loss(p):
        out = per_sample_loss(p, zb, yb)
        per_row, stats = out if isinstance(out, tuple) else (out, {})
        return jnp.sum(per_row * mask), stats

    (val, stats), q = jax.value_and_grad(batch_sum_loss, has_aux=True)(params)
    return q, val, stats


def sample_batches(data: SampleFedData, key, batch_size: int):
    """Step 4: each client randomly selects a mini-batch N_i^(t). Keys are
    derived per client id (`client_keys`) so the cohort engine draws the
    same batch for the same client."""
    keys = client_keys(key, jnp.arange(data.num_clients))

    def pick(k, count):
        return jax.random.randint(k, (batch_size,), 0, count)

    return jax.vmap(pick)(keys, data.counts)        # (I, B)


def batch_mask(counts, batch_size: int):
    """(I, B) validity mask for ragged clients: client i fills min(B, N_i)
    batch slots; a client with N_i < B contributes a smaller sum (its
    aggregation weight uses B_i = min(B, N_i), see `aggregation_weights`).
    For B <= min_i N_i this is all-ones and the dense path is recovered
    bit-for-bit."""
    b_i = jnp.minimum(counts, batch_size)                       # (I,)
    return (jnp.arange(batch_size)[None, :] < b_i[:, None]).astype(jnp.float32)


def participation_mask(key, num_clients: int, participation: int):
    """0/1 mask selecting S = `participation` of I clients uniformly without
    replacement (each client included w.p. S/I).

    The selection is ``cohort_sample`` — O(S) RNG work, not the former
    O(I log I) full permutation — scattered into a dense mask. The dense
    engine and the cohort engine therefore draw the SAME S clients from the
    same key, which is what makes their trajectories comparable."""
    sel = cohort_sample(key, num_clients, participation)
    return jnp.zeros((num_clients,), jnp.float32).at[sel].set(1.0)


def aggregation_weights(counts, batch_size: int, part_mask=None):
    """Server weights w_i applied to the q-uploads.

    Dense full participation: w_i = N_i/(B_i·N) with B_i = min(B, N_i)
    (the paper's N_i/(BN), generalized to ragged clients). Under partial
    participation (mask m selecting S of I clients) the weights become
    m_i·(I/S)·N_i/(B_i·N) — a Horvitz-Thompson estimator, unbiased because
    E[m_i] = S/I exactly cancels the I/S inflation."""
    counts = counts.astype(jnp.float32)
    b_i = jnp.minimum(counts, batch_size)
    w = counts / (b_i * jnp.sum(counts))
    if part_mask is not None:
        scale = counts.shape[0] / jnp.sum(part_mask)
        w = w * part_mask * scale
    return w


def sample_round(per_sample_loss: Callable, params, data: SampleFedData, key,
                 batch_size: int, with_value: bool = False,
                 participation: int | None = None, participation_key=None,
                 codec=None, ef=None, codec_key=None, topology=None,
                 dp=None, dp_key=None):
    """Computes client uploads q_i = Σ_{n∈batch} ∇f(ω;x_n) (and Σ f if asked)
    then the server aggregate ĝ = Σ_i N_i/(B_i·N) q_i  (and F̂ likewise).

    Ragged clients (N_i < B) contribute masked batches of B_i = min(B, N_i)
    samples. With `participation` = S < I, only S uniformly-drawn clients are
    aggregated this round, reweighted by I/S so the estimate stays unbiased
    (this simulation still *computes* every client's q with static shapes and
    zero-masks the rest at the server; a deployment would skip the work).

    With `codec=` each client flattens its q pytree to one (P,) vector and
    uploads the codec's wire format instead of dense fp32; `ef` is the
    (I, P) error-feedback residual matrix from the previous round (zeros if
    None) and the updated residuals come back as ``uploads["ef"]``.
    Non-participating clients neither upload nor touch their residual.

    ``topology=`` selects WHERE the clients execute (core/topology.py,
    DESIGN.md §11): None/`LocalTopology` vmaps all I clients on one device
    (the reference engine); a `ShardedTopology` distributes them over the
    mesh's client axes via shard_map, with this same aggregation realized as
    a weighted `lax.psum` and the codec/EF roundtrip applied per shard
    *before* the collective. Batch selection, participation draw, and codec
    keys are computed identically for every topology, so trajectories agree
    up to float reassociation.

    With ``dp=`` (a repro.core.privacy.DPConfig) each client's flat
    q-upload is clipped to ``dp.clip_norm`` at B_i-mean scale and Gaussian-
    noised at the analytic σ BEFORE any codec encode (DESIGN.md §15) — the
    wire format, bytes accounting, and EF residual see the privatized
    upload, and under a sharded topology the noise is added per shard
    before the psum. Noise keys derive from the STABLE client id
    (`client_keys`), so the dense and cohort engines draw identical noise
    for the same client; ``dp_key`` overrides the derivation base.
    Per-client clip/noise statistics come back as ``uploads["dp"]``.

    Returns (grad_est, value_est, uploads) — `uploads` is everything that
    crossed the client boundary (privacy-surface assertion hook); with a
    codec that is ``uploads["encoded"]`` (wire format) and
    ``uploads["upload_nbytes"]`` (exact bytes, repro.comm.accounting).
    """
    if participation is not None and participation < 1:
        raise ValueError(f"participation must be >= 1, got {participation}")
    _check_codec_args("sample_round", codec, ef)
    if codec is not None:
        _check_ef_shape("sample_round", "q_grad", ef,
                        (data.num_clients, comm_codecs.tree_flat_dim(params)))
    topo = topology if topology is not None else topology_lib.LOCAL
    with obs_trace.phase("batch-select"):
        idx = sample_batches(data, key, batch_size)      # (I, B)
        bmask = batch_mask(data.counts, batch_size)      # (I, B)

    def client(feat_i, lab_i, idx_i, mask_i):
        zb = jnp.take(feat_i, idx_i, axis=0)
        yb = jnp.take(lab_i, idx_i, axis=0)
        return _client_upload(per_sample_loss, params, zb, yb, mask_i)

    pmask = None
    # S >= I degrades to full participation (the I/S reweighting is exactly 1)
    if participation is not None and participation < data.num_clients:
        if participation_key is None:
            participation_key = jax.random.fold_in(key, 0x5ca)
        pmask = participation_mask(participation_key, data.num_clients,
                                   participation)
    ckeys = active = None
    nbytes = None
    if codec is not None:
        if codec_key is None:
            codec_key = jax.random.fold_in(key, 0xC0DEC)
        ckeys = client_keys(codec_key, jnp.arange(data.num_clients))
        active = pmask if pmask is not None else jnp.ones((data.num_clients,))
        nbytes = comm_accounting.sample_round_bytes(
            comm_codecs.tree_flat_dim(params), data.num_clients, codec,
            participation=participation, with_value=with_value)["up"]
    dkeys = dscale = None
    if dp is not None:
        if dp_key is None:
            dp_key = jax.random.fold_in(key, 0xD9)
        dkeys = client_keys(dp_key, jnp.arange(data.num_clients))
        # clip at the client's B_i-MEAN scale (C is a per-example-scale
        # constant); the stage rescales to the B_i-sum afterwards so the
        # eq.-(9) weights are untouched
        dscale = 1.0 / jnp.minimum(data.counts.astype(jnp.float32),
                                   float(batch_size))
    w = aggregation_weights(data.counts, batch_size, pmask)
    s = topo.weighted_sum(client, (data.features, data.labels, idx, bmask), w,
                          codec=codec, ef=ef, codec_keys=ckeys, active=active,
                          dp=dp, dp_keys=dkeys, dp_scale=dscale)
    uploads = {"q_grad_sums": s.uploads,
               "q_value_sums": s.values if with_value else None,
               "client_stats": s.aux, "participants": pmask,
               "encoded": s.encoded, "ef": s.ef,
               "dp": s.dp, "upload_nbytes": nbytes}
    return s.weighted, s.value, uploads


def cohort_weights(counts_s, batch_size: int, num_clients: int, total):
    """Horvitz-Thompson server weights for the S-client cohort:
    w_i = (I/S)·N_i/(B_i·N). Identical numbers to the non-zero entries of
    ``aggregation_weights(counts, B, pmask)`` on the dense path — the cohort
    engine just never materializes the zeros."""
    counts_s = counts_s.astype(jnp.float32)
    b_i = jnp.minimum(counts_s, batch_size)
    scale = num_clients / counts_s.shape[0]
    return scale * counts_s / (b_i * total)


def cohort_round(per_sample_loss: Callable, params, data, key,
                 batch_size: int, cohort: int, with_value: bool = False,
                 participation_key=None, codec=None, ef=None, codec_key=None,
                 topology=None, dp=None, dp_key=None):
    """Participant-only O(S) realization of :func:`sample_round` under
    partial participation (DESIGN.md §14).

    Where ``sample_round(participation=S)`` computes all I clients and
    zero-masks I−S of them server-side, this draws the S-client cohort in
    O(S) work (`cohort_sample`), gathers ONLY the cohort's data shards
    (``data.batch_rows`` — a `SampleFedData` gathers rows, a
    `data.synthetic.VirtualFedData` generates them from the client id, so
    I = 1e6 never materializes anything population-sized), and runs client
    compute, codec encode, and the eq.-(9) weighted aggregation over the
    (S, ...) cohort axis. Per-round compute and carried state scale with S.

    Equality contract (pinned in tests/test_cohort.py and
    benchmarks/scale_bench.py): with the same `key`/`participation_key`/
    `codec_key`, the same clients are drawn (`participation_mask` scatters
    the same `cohort_sample` ids), each drawn client derives the same batch
    and codec keys (`client_keys` folds in the stable client id), and the
    Horvitz-Thompson weights match the dense masked weights entry-for-entry
    — so grad/value estimates agree with the dense engine up to float
    reassociation (atol 1e-5: an S-term sum vs an I-term sum with zeros).

    ``ef`` is a :class:`repro.comm.error_feedback.EFStore` holding the
    (I, P) residual backing; only the cohort's (S, P) slice is gathered
    into the round and scattered back — non-participants' residuals are
    never touched (bit-frozen by construction, not by masking). The updated
    store comes back as ``uploads["ef"]``.

    ``topology=`` shards the COHORT axis: a `ShardedTopology` splits the S
    participants over the mesh (S must divide by the shard count), so
    population size never constrains the mesh fit.

    ``dp=`` privatizes the cohort's uploads exactly as in
    :func:`sample_round` — O(S) clip+noise work with noise keys derived
    from the STABLE client id, so the dense engine's noise for the same
    drawn client is identical and the two trajectories keep agreeing at
    atol 1e-5. The S-of-I draw is also what earns the accountant's
    subsampling amplification (privacy.rdp_per_round at q = S/I).
    Per-client stats come back as ``uploads["dp"]`` ((S,)-shaped).

    Returns (grad_est, value_est, uploads); ``uploads["cohort"]`` is the
    (S,) drawn client ids — the O(S) analog of the dense path's
    ``uploads["participants"]`` mask.
    """
    _check_codec_args("cohort_round", codec, ef)
    topo = topology if topology is not None else topology_lib.LOCAL
    num_clients = data.num_clients
    if participation_key is None:
        participation_key = jax.random.fold_in(key, 0x5ca)
    with obs_trace.phase("cohort-select"):
        ids = cohort_sample(participation_key, num_clients, cohort)   # (S,)
        counts_s = data.counts_for(ids)                               # (S,)
    with obs_trace.phase("batch-select"):
        bkeys = client_keys(key, ids)
        idx = jax.vmap(
            lambda k, c: jax.random.randint(k, (batch_size,), 0, c)
        )(bkeys, counts_s)                                            # (S, B)
        bmask = batch_mask(counts_s, batch_size)                      # (S, B)
        zb, yb = data.batch_rows(ids, idx)            # (S, B, P), (S, B, L)

    def client(zb_i, yb_i, mask_i):
        return _client_upload(per_sample_loss, params, zb_i, yb_i, mask_i)

    ckeys = active = ef_rows = None
    nbytes = None
    if codec is not None:
        dim = comm_codecs.tree_flat_dim(params)
        if ef is not None:
            if not hasattr(ef, "gather"):
                raise ValueError(
                    "cohort_round: ef must be a keyed "
                    "repro.comm.error_feedback.EFStore (ef_store_init), not "
                    f"a dense residual array — got {type(ef).__name__}")
            _check_ef_shape("cohort_round", "q_grad", ef.data,
                            (num_clients, dim))
            with obs_trace.phase("ef-gather"):
                ef_rows = ef.gather(ids, topo.mesh)                   # (S, P)
        if codec_key is None:
            codec_key = jax.random.fold_in(key, 0xC0DEC)
        ckeys = client_keys(codec_key, ids)
        active = jnp.ones((cohort,), jnp.float32)
        nbytes = comm_accounting.sample_round_bytes(
            dim, num_clients, codec, participation=cohort,
            with_value=with_value)["up"]
    dkeys = dscale = None
    if dp is not None:
        if dp_key is None:
            dp_key = jax.random.fold_in(key, 0xD9)
        dkeys = client_keys(dp_key, ids)      # stable ids == dense engine
        dscale = 1.0 / jnp.minimum(counts_s.astype(jnp.float32),
                                   float(batch_size))
    w = cohort_weights(counts_s, batch_size, num_clients, data.total)
    s = topo.weighted_sum(client, (zb, yb, bmask), w, codec=codec,
                          ef=ef_rows, codec_keys=ckeys, active=active,
                          dp=dp, dp_keys=dkeys, dp_scale=dscale)
    new_ef = s.ef
    if codec is not None and ef is not None:
        with obs_trace.phase("ef-scatter"):
            new_ef = ef.scatter(ids, s.ef, topo.mesh)
    uploads = {"q_grad_sums": s.uploads,
               "q_value_sums": s.values if with_value else None,
               "client_stats": s.aux, "cohort": ids,
               "encoded": s.encoded, "ef": new_ef,
               "dp": s.dp, "upload_nbytes": nbytes}
    return s.weighted, s.value, uploads


# ---------------------------------------------------------------------------
# feature-based rounds (Algorithm 3/4 steps 3-6) — the paper's MLP composition
# ---------------------------------------------------------------------------


def feature_round(params, data: FeatureFedData, key, batch_size: int,
                  head_loss_from_h: Callable, client_h: Callable,
                  codec=None, ef=None, codec_key=None, topology=None,
                  dp=None, dp_key=None):
    """Faithful Alg-3 information flow for f(ω;x) = g0(ω0, Σ_i h_i(ω_i, x_i)):

      server picks N^(t)  →  client i computes h_i and broadcasts it  →
      any client computes q_{f,0,0} = Σ_n ∇_{ω0} f  →  each client i computes
      q_{f,0,i} = Σ_n ∇_{ω_i} f from (ω0, its block, all h_j)  →  server
      aggregates with 1/B weights (eq. 16).

    params: {"w0": head params, "blocks": (I, ...) client blocks}.
    With `codec=` the q_{f,0,0} head upload and each client's q_{f,0,i}
    block upload cross the wire compressed, with error-feedback residuals
    ``ef = {"w0": (P0,), "blocks": (I, Pb)}`` (the step-4 h-exchange stays
    dense — it feeds gradients, not the aggregate, and is accounted in
    repro.comm.accounting.feature_round_bytes).

    ``topology=`` selects WHERE the feature clients execute (DESIGN.md §12):
    None/`LocalTopology` vmaps all I clients on one device (the reference
    engine); a `ShardedTopology` built over a "model"-axis mesh
    (`launch.mesh.make_feature_mesh`) places each client on its own shard,
    with the h-exchange realized as a tiled `lax.all_gather` — bit-identical
    h_sum, hence bit-identical gradients and wire formats across topologies.
    Batch selection and codec keys are computed identically for every
    topology.

    With ``dp=`` the two q-upload streams — the head q_{f,0,0} and each
    client's block q_{f,0,i} — are clipped at B-mean scale and Gaussian-
    noised BEFORE any codec encode, exactly as in :func:`sample_round`
    (DESIGN.md §15). The step-4 h-exchange is NOT privatized here: it is a
    per-round activation broadcast, not an aggregate release, and a
    deployment would need a separate mechanism for it (documented
    limitation). Per-stream stats come back as ``uploads["dp"]``.

    Returns (grad_est pytree like params, value_est, uploads).
    """
    _check_codec_args("feature_round", codec, ef)
    topo = topology if topology is not None else topology_lib.LOCAL
    n = data.total
    with obs_trace.phase("batch-select"):
        idx = jax.random.randint(key, (batch_size,), 0, n)        # server-chosen
        yb = jnp.take(data.labels, idx, axis=0)
        zb = jnp.take(data.feature_blocks, idx, axis=1)           # (I, B, P_i)

    def head_sum_loss(w0, h_sum_):
        return jnp.sum(head_loss_from_h(w0, h_sum_, yb))

    # step 5: q_{f,0,0} — head gradient from aggregated h only; the closure
    # over (params["w0"], yb) is replicated compute under a sharded topology
    def head_fn(h_sum):
        val, q00 = jax.value_and_grad(head_sum_loss)(params["w0"], h_sum)
        # step 6's upstream: dl/dh backpropagated through the aggregate
        dl_dh = jax.grad(lambda hs: head_sum_loss(params["w0"], hs))(h_sum)
        return val, q00, dl_dh

    # step 6: q_{f,0,i} — via chain rule through client i's own h_i
    def block_grad(block_i, zb_i, dl_dh):
        _, vjp = jax.vjp(lambda bl: client_h(bl, zb_i), block_i)
        return vjp(dl_dh)[0]

    head_key = block_keys = None
    nbytes = None
    d_head = d_block = None
    if codec is not None:
        d_head = comm_codecs.tree_flat_dim(params["w0"])
        d_block = comm_codecs.tree_flat_dim(params["blocks"], stacked=True)
        if ef is not None:
            if not isinstance(ef, dict) or set(ef) != {"w0", "blocks"}:
                raise ValueError(
                    "feature_round: ef must be a dict with 'w0' and 'blocks' "
                    f"residual streams (repro.comm ef_init/ef_init_stacked), "
                    f"got {sorted(ef) if isinstance(ef, dict) else type(ef).__name__}")
            _check_ef_shape("feature_round", "w0", ef["w0"], (d_head,))
            _check_ef_shape("feature_round", "blocks", ef["blocks"],
                            (data.num_clients, d_block))
        if codec_key is None:
            codec_key = jax.random.fold_in(key, 0xC0DEC)
        head_key = jax.random.fold_in(codec_key, 0)
        block_keys = client_keys(jax.random.fold_in(codec_key, 1),
                                 jnp.arange(data.num_clients))
    dp_head_key = dp_block_keys = None
    if dp is not None:
        if dp_key is None:
            dp_key = jax.random.fold_in(key, 0xD9)
        dp_head_key = jax.random.fold_in(dp_key, 0)
        dp_block_keys = client_keys(jax.random.fold_in(dp_key, 1),
                                    jnp.arange(data.num_clients))

    s = topo.feature_sum(client_h, head_fn, block_grad, params["blocks"], zb,
                         codec=codec, ef=ef, head_key=head_key,
                         block_keys=block_keys, dp=dp,
                         dp_head_key=dp_head_key, dp_block_keys=dp_block_keys,
                         dp_scale=1.0 / batch_size)
    if codec is not None:
        nbytes = comm_accounting.feature_round_bytes(
            d_head, [d_block] * data.num_clients, batch_size,
            s.h.shape[-1], data.num_clients, codec)["up"]

    grad_est = {"w0": s.q_head / batch_size,
                "blocks": s.q_blocks / batch_size}
    value_est = s.value / batch_size
    uploads = {"h_exchange": s.h, "q_head": s.q_head, "q_blocks": s.q_blocks,
               "encoded": s.encoded, "ef": s.ef, "dp": s.dp,
               "upload_nbytes": nbytes}
    return grad_est, value_est, uploads


# Fig.-3 float counters: moved to repro.comm.accounting (which adds the
# byte-level, codec-aware versions); re-exported here for back-compat.
comm_load_per_round = comm_accounting.comm_load_per_round
