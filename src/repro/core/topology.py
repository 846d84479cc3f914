"""Topology layer: WHERE the paper's clients execute (DESIGN.md §11).

The sample-based protocol (Algorithms 1/2, the SGD baselines, the
local-update extension) has one structural invariant: every round is

    per-client compute  →  per-client upload (optionally DP clip+noised,
    then codec+EF compressed, at the client boundary)  →  server weighted
    sum  Σ_i w_i û_i

with w_i = N_i/(B_i·N) (eq. 9's aggregation, generalized to ragged clients
and Horvitz-Thompson participation reweighting). This module abstracts that
shape behind one contract, ``weighted_sum``, with two realizations.

``weighted_sum`` is leading-axis-generic: the dense engine passes
(I, ...)-leading args (every client in the population), the O(S) cohort
engine (``fed.cohort_round``, DESIGN.md §14) passes the (S, ...)-leading
cohort slice — client execution, codec encode, and the weighted psum then
run over S participants only, and a `ShardedTopology` shards the COHORT
(S must divide the shard count; population size never constrains the mesh).
The two realizations:

* :class:`LocalTopology` — all I clients on one device, `jax.vmap` over the
  client axis, `jnp.tensordot` for the server sum. Bit-for-bit the engine
  the repo has always run; kept as the equivalence reference.
* :class:`ShardedTopology` — clients distributed over the mesh's
  ("pod","data") axes via `jax.shard_map`: each device vmaps
  its I/D resident clients, applies the codec encode + error-feedback
  residual update *per shard before any collective* (compression happens at
  the client boundary, exactly as in the simulation), reduces its local
  Σ w_i û_i partial, and the eq.-(9) server aggregation is realized as a
  weighted `lax.psum` over the client axes. Per-client state (EF residuals,
  uploads) never leaves its shard; only the B-summed, weighted q-statistics
  cross devices — the mesh realization of the paper's model-aggregation
  privacy argument.

Both topologies compose with the scan-compiled round driver
(`core/rounds.py`): the shard_map sits inside the scanned step, so a K-round
epoch is still ONE dispatch, now spanning D devices, with the per-client EF
residuals riding the scan carry sharded over clients
(`ShardedTopology.place_state` pre-places them).

Equivalence: sharded == local up to float reassociation (per-device partial
sums + psum vs one tensordot); `tests/test_topology.py` pins the trajectory
at atol 1e-5 with codec=int8 + error feedback + partial participation all
enabled at once.

The feature-based protocol (Algorithms 3/4, vertical FL, DESIGN.md §12) has
a different structural invariant — the clients hold feature *blocks*, not
sample shards, and the round is

    client i computes h_i(ω_i, x_i)  →  h-exchange (every client sees all
    h_j)  →  head gradient q_{f,0,0} from Σ h  →  per-client block gradients
    q_{f,0,i} via the chain rule through the client's OWN h_i

— realized here by the second contract, ``feature_sum``. The sharded
realization places feature clients on the mesh's "model" axis
(`launch.mesh.make_feature_mesh`) and implements the paper's step-4
h-broadcast as a tiled `lax.all_gather`: every shard reassembles the full
(I, B, J) h in canonical client order, so Σ_i h_i — and hence the head
gradient, the backpropagated dl/dh, the block gradients, and the codec wire
formats — is bit-identical to the local vmap reference, not merely close.
The head computation is replicated (every client CAN compute it from the
broadcast h's; a deployment would let the fastest one), the block gradients
never leave their shard, and the codec + error-feedback roundtrip runs per
shard exactly like the sample-based path.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.comm import codecs as comm_codecs
from repro.comm import error_feedback as comm_ef
from repro.core import privacy as privacy_lib
from repro.obs import trace as obs_trace


class ClientSums(NamedTuple):
    """Everything a round produces at and across the client boundary."""
    weighted: object          # Σ_i w_i û_i — server aggregate (pytree)
    value: jnp.ndarray        # Σ_i w_i val_i — scalar aggregate
    uploads: object           # per-client û_i, stacked (I, ...) pytree
                              # (None when the sum ran in client blocks)
    values: jnp.ndarray       # per-client val_i, (I,)
    encoded: object           # codec wire format per client (None if dense)
    ef: object                # updated EF residuals (I, P) (None if dense)
    dp: object = None         # clip/noise stats per client (None if no DP)
    aux: object = None        # per-client stats the client_fn returned, (I,)


# the stacked uploads of one block of clients may take this share of the
# device's memory; LocalTopology sums larger cohorts block by block
UPLOAD_BLOCK_SHARE = 1 / 8


@functools.cache
def device_bytes() -> int:
    """Memory of the device the clients run on (16 GiB where the backend
    keeps no statistics, as the CPU backend); read once, since
    `rounds.donates` asks on every dispatch."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", 16 * 2**30))


def client_block(num_clients: int, upload_bytes: int, budget=None) -> int:
    """Clients per block of the server sum: the largest divisor of
    ``num_clients`` whose stacked uploads (``upload_bytes`` each) fit in
    ``budget`` (default: UPLOAD_BLOCK_SHARE of the device), at least 1."""
    if budget is None:
        budget = UPLOAD_BLOCK_SHARE * device_bytes()
    blk = num_clients
    while blk > 1 and (blk * upload_bytes > budget or num_clients % blk):
        blk -= 1
    return blk


def _run_clients(client_fn, args):
    """vmap of client_fn, which returns (upload, val) or (upload, val, aux):
    (uploads, values, aux) stacked over the client axis."""
    out = jax.vmap(client_fn)(*args)
    return out if len(out) == 3 else (*out, {})


def _lanes(client_fn, codec, dp, args, weights, ef, codec_keys, active,
           dp_keys, dp_scale):
    """The per-client stages over a stack of clients, then their weighted
    sum: client compute, DP clip + noise, codec encode with EF, Σ w_i û_i.
    Returns the fields of :class:`ClientSums` in order. Identical code runs
    for LocalTopology (whole or per block) and inside each shard_map
    shard."""
    with obs_trace.phase("client-compute"):
        uploads, values, aux = _run_clients(client_fn, args)
    enc = new_ef = dp_stats = None
    if dp is not None:
        with obs_trace.phase("dp-privatize"):
            uploads, dp_stats = _privatize_stacked(dp, uploads, dp_keys,
                                                   dp_scale)
    if codec is not None:
        with obs_trace.phase("codec-encode"):
            enc, uploads, new_ef = _compress_stacked(codec, uploads, ef,
                                                     codec_keys, active)
    with obs_trace.phase("aggregate"):
        weighted, value = _weighted(weights, uploads, values)
    return weighted, value, uploads, values, enc, new_ef, dp_stats, aux


def _compress_stacked(codec, uploads, ef, codec_keys, active):
    """Shared client-boundary compression: flatten each client's upload to
    one (P,) vector, run the codec through an error-feedback roundtrip, and
    hand back the decoded uploads the server will aggregate. Identical code
    runs under local vmap and inside each shard_map shard — the client
    boundary does not move with the topology."""
    uf, unflatten = comm_codecs.flatten_stacked(uploads)
    if ef is None:
        ef = jnp.zeros_like(uf)
    if active is None:
        active = jnp.ones((uf.shape[0],), jnp.float32)
    enc, u_hat, new_ef = jax.vmap(
        lambda x, r, k, a: comm_ef.ef_roundtrip(codec, x, r, k, a)
    )(uf, ef, codec_keys, active)
    return enc, unflatten(u_hat), new_ef


def _privatize_stacked(dp, uploads, dp_keys, dp_scale):
    """Shared client-boundary DP stage (DESIGN.md §15): flatten each
    client's upload to one (P,) vector and clip+noise it at mean scale
    (``dp_scale`` = 1/B_i converts the B_i-sum; None = already means).
    Runs BEFORE :func:`_compress_stacked`, so the codec wire format, the
    bytes accounting, and the EF residual all see the privatized upload.
    Identical code under local vmap and inside each shard_map shard — the
    sharded psum aggregates already-noised contributions."""
    uf, unflatten = comm_codecs.flatten_stacked(uploads)
    priv, stats = privacy_lib.clip_and_noise(uf, dp_keys, dp, dp_scale)
    return unflatten(priv), stats


class FeatureSums(NamedTuple):
    """Everything an Algorithm-3/4 vertical round produces at and across the
    client boundary (the feature-based analog of :class:`ClientSums`)."""
    h: object                 # per-client h_i, (I, B, J) — the h-exchange
    h_sum: jnp.ndarray        # Σ_i h_i, (B, J), replicated
    value: jnp.ndarray        # head batch value Σ_n f (scalar)
    q_head: object            # q_{f,0,0} head upload (decoded if codec)
    q_blocks: object          # q_{f,0,i} block uploads, (I, ...) pytree
    encoded: object           # {"q_head","q_blocks"} wire formats (None dense)
    ef: object                # {"w0": (P0,), "blocks": (I, Pb)} residuals
    dp: object = None         # clip/noise stats per stream (None if no DP)


def _compress_feature(codec, q_head, q_blocks, ef, head_key, block_keys):
    """Client-boundary compression for the feature-based uploads: ONE head
    stream (q_{f,0,0}, uploaded by the client that computed it) plus one
    stream per client block (q_{f,0,i}), each through its own error-feedback
    roundtrip. Identical code runs under local vmap and inside each
    shard_map shard; under the sharded topology the head roundtrip is
    replicated compute on bit-identical inputs (same key), so its wire
    format agrees across every shard."""
    f0, unf0 = comm_codecs.flatten_tree(q_head)
    fb, unfb = comm_codecs.flatten_stacked(q_blocks)
    if ef is None:
        ef = {"w0": jnp.zeros_like(f0), "blocks": jnp.zeros_like(fb)}
    enc0, h0, r0 = comm_ef.ef_roundtrip(codec, f0, ef["w0"], head_key)
    encb, hb, rb = jax.vmap(
        lambda x, r, k: comm_ef.ef_roundtrip(codec, x, r, k)
    )(fb, ef["blocks"], block_keys)
    return ({"q_head": enc0, "q_blocks": encb}, unf0(h0), unfb(hb),
            {"w0": r0, "blocks": rb})


def _privatize_feature(dp, q_head, q_blocks, dp_head_key, dp_block_keys,
                       dp_scale):
    """Client-boundary DP stage for the feature-based uploads: the ONE head
    stream (q_{f,0,0}) plus one stream per client block (q_{f,0,i}), each
    clipped and noised at mean scale (``dp_scale`` = 1/B — the uploads are
    batch sums) BEFORE :func:`_compress_feature`. Under the sharded
    topology the head stage is replicated compute on bit-identical inputs
    (same key → same noise), so every shard agrees; the step-4 h-exchange
    itself is NOT privatized (it feeds gradients, not the released
    aggregate — documented in DESIGN.md §15)."""
    f0, unf0 = comm_codecs.flatten_tree(q_head)
    p0, st0 = privacy_lib.clip_and_noise(
        f0[None], dp_head_key[None], dp, jnp.full((1,), dp_scale))
    fb, unfb = comm_codecs.flatten_stacked(q_blocks)
    pb, stb = privacy_lib.clip_and_noise(
        fb, dp_block_keys, dp, jnp.full((fb.shape[0],), dp_scale))
    stats = {"head_clipped": st0["clipped"][0],
             "head_noise_sq": st0["noise_sq"][0],
             "blocks_clipped": stb["clipped"],
             "blocks_noise_sq": stb["noise_sq"]}
    return unf0(p0[0]), unfb(pb), stats


def _weighted(weights, uploads, values):
    weighted = jax.tree.map(
        lambda u: jnp.tensordot(weights, u.astype(jnp.float32), axes=1),
        uploads)
    return weighted, jnp.dot(weights, values)


class LocalTopology:
    """All clients on one device: vmap over the client axis (the reference
    engine — every sharded result is pinned against this one)."""

    name = "local"
    num_shards = 1
    mesh = None

    def weighted_sum(self, client_fn: Callable, args, weights, *,
                     codec=None, ef=None, codec_keys=None, active=None,
                     dp=None, dp_keys=None, dp_scale=None) -> ClientSums:
        """client_fn(*per_client_args) -> (upload pytree, val scalar[, aux
        stats]); args are (I, ...)-leading arrays; returns all of
        :class:`ClientSums`. With ``dp=`` (a privacy.DPConfig) each
        client's upload is clipped+noised at the client boundary BEFORE any
        codec encode.

        Where the I stacked uploads would not fit in UPLOAD_BLOCK_SHARE of
        the device (I·P·4 bytes), the clients run in blocks under a scan
        that keeps a running Σ w_i û_i: DP, codec and EF stay per client
        inside a block, so the wire bytes are the same, and ``uploads``
        comes back None. Otherwise it is one block, the plain vmap."""
        def lanes(*per_client):
            return _lanes(client_fn, codec, dp, *per_client)

        num = weights.shape[0]
        one = jax.eval_shape(client_fn, *[a[0] for a in args])[0]
        blk = client_block(num, 4 * comm_codecs.tree_flat_dim(one))
        per_client = (tuple(args), weights, ef, codec_keys, active, dp_keys,
                      dp_scale)
        if blk == num:
            return ClientSums(*lanes(*per_client))

        def body(acc, xs):
            weighted, _, _, *rest = lanes(*xs)
            with obs_trace.phase("aggregate"):
                acc = jax.tree.map(jnp.add, acc, weighted)
            return acc, rest

        blocks = jax.tree.map(
            lambda a: a.reshape(num // blk, blk, *a.shape[1:]), per_client)
        zero = jax.tree.map(lambda u: jnp.zeros(u.shape, jnp.float32), one)
        weighted, rest = jax.lax.scan(body, zero, blocks)
        values, enc, new_ef, dp_stats, aux = jax.tree.map(
            lambda a: a.reshape(num, *a.shape[2:]), rest)
        with obs_trace.phase("aggregate"):
            value = jnp.dot(weights, values)
        return ClientSums(weighted=weighted, value=value, uploads=None,
                          values=values, encoded=enc, ef=new_ef, dp=dp_stats,
                          aux=aux)

    def feature_sum(self, h_fn: Callable, head_fn: Callable,
                    block_grad_fn: Callable, blocks, zb, *,
                    codec=None, ef=None, head_key=None, block_keys=None,
                    dp=None, dp_head_key=None, dp_block_keys=None,
                    dp_scale=1.0) -> FeatureSums:
        """Alg-3/4 information flow, all clients on one device.

        h_fn(block_i, zb_i) -> (B, J) per-client h; head_fn(h_sum) ->
        (value, q_head, dl_dh) closes over the head params and labels;
        block_grad_fn(block_i, zb_i, dl_dh) -> q_{f,0,i}. blocks/zb are
        (I, ...)-leading. With ``dp=`` the head + block q-uploads are
        clipped+noised before any codec encode (the h-exchange stays in
        the clear — DESIGN.md §15). This vmap path is the bit-level
        reference every sharded result is pinned against."""
        with obs_trace.phase("client-compute"):
            h = jax.vmap(h_fn)(blocks, zb)                   # (I, B, J)
        with obs_trace.phase("aggregate"):
            h_sum = jnp.sum(h, axis=0)
        with obs_trace.phase("head-compute"):
            value, q_head, dl_dh = head_fn(h_sum)
        with obs_trace.phase("client-compute"):
            q_blocks = jax.vmap(block_grad_fn, in_axes=(0, 0, None))(
                blocks, zb, dl_dh)
        enc = new_ef = dp_stats = None
        if dp is not None:
            with obs_trace.phase("dp-privatize"):
                q_head, q_blocks, dp_stats = _privatize_feature(
                    dp, q_head, q_blocks, dp_head_key, dp_block_keys,
                    dp_scale)
        if codec is not None:
            with obs_trace.phase("codec-encode"):
                enc, q_head, q_blocks, new_ef = _compress_feature(
                    codec, q_head, q_blocks, ef, head_key, block_keys)
        return FeatureSums(h=h, h_sum=h_sum, value=value, q_head=q_head,
                           q_blocks=q_blocks, encoded=enc, ef=new_ef,
                           dp=dp_stats)

    def place_state(self, state):
        """No placement to do on a single device."""
        return state

    def place_feature_state(self, state):
        """No placement to do on a single device."""
        return state


class ShardedTopology:
    """Clients distributed over the mesh's client axes via shard_map; the
    eq.-(9) server aggregation is a weighted `lax.psum`.

    mesh: a `jax.sharding.Mesh` whose client axes (default: the ("pod",
    "data") axes present, else all axes) carry the clients. The client count
    I must be divisible by the product of the client-axis sizes D; each
    device executes I/D clients.
    """

    name = "sharded"

    def __init__(self, mesh, axes: Optional[Sequence[str]] = None):
        self.mesh = mesh
        if axes is None:
            axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
            axes = axes or tuple(mesh.axis_names)
        self.axes = tuple(axes)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.num_shards = math.prod(sizes[a] for a in self.axes)

    def _check_divisible(self, num_clients: int):
        if num_clients % self.num_shards:
            raise ValueError(
                f"num_clients={num_clients} must be divisible by the "
                f"{self.num_shards} client shards of mesh axes {self.axes} "
                "(pad the client set or pick a smaller mesh)")

    def client_sharding(self):
        """NamedSharding placing a leading client axis over this topology's
        mesh axes (used to pre-place datasets and EF carries)."""
        return jax.sharding.NamedSharding(self.mesh, P(self.axes))

    def place_state(self, state):
        """Pre-place the per-client EF residuals of a `CommCarry` scan state
        over the client axes, so the carry starts (and stays) sharded across
        the K scanned rounds instead of being resharded on first use."""
        if not isinstance(state, comm_ef.CommCarry) or state.ef is None:
            return state
        sh = self.client_sharding()

        def put(x):
            # a keyed EFStore (cohort engine, DESIGN.md §14) is indexed by
            # POPULATION id — what shards is the (S, P) cohort slice inside
            # weighted_sum, so the backing stays replicated/default-placed
            if isinstance(x, comm_ef.EFStore):
                return x
            if (hasattr(x, "ndim") and x.ndim >= 1
                    and x.shape[0] % self.num_shards == 0):
                return jax.device_put(x, sh)
            return x

        return state._replace(
            ef=jax.tree.map(put, state.ef,
                            is_leaf=lambda v: isinstance(v, comm_ef.EFStore)))

    def weighted_sum(self, client_fn: Callable, args, weights, *,
                     codec=None, ef=None, codec_keys=None, active=None,
                     dp=None, dp_keys=None, dp_scale=None) -> ClientSums:
        """Same contract as :meth:`LocalTopology.weighted_sum`, executed
        shard-locally with the server sum as a weighted psum. The DP
        clip+noise stage, codec encode, and EF update all run per shard
        BEFORE the collective: each shard noises its own resident clients'
        uploads, so the psum aggregates already-noised contributions and
        what crosses the device boundary is the already-weighted decoded
        privatized aggregate — the wire format / residuals stay
        client-resident."""
        self._check_divisible(weights.shape[0])
        axes = self.axes
        spec = P(axes)

        def body(*per_client):
            partial, val_partial, *rest = _lanes(client_fn, codec, dp,
                                                 *per_client)
            with obs_trace.phase("collective"):
                weighted = jax.lax.psum(partial, axes)
                value = jax.lax.psum(val_partial, axes)
            return (weighted, value, *rest)

        sharded = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(spec, spec, spec, spec, spec, spec, spec),
            out_specs=(P(), P(), spec, spec, spec, spec, spec, spec),
            check_vma=False)
        return ClientSums(*sharded(tuple(args), weights, ef, codec_keys,
                                   active, dp_keys, dp_scale))

    def place_feature_state(self, state):
        """Pre-place a feature-based `CommCarry`'s EF residual dict: the
        per-client block residuals (I, Pb) shard over the client axes, the
        single head stream (P0,) stays replicated — matching feature_sum's
        out_specs so the scan carry never reshards."""
        if (not isinstance(state, comm_ef.CommCarry)
                or not isinstance(state.ef, dict)):
            return state
        sh = self.client_sharding()
        rep = jax.sharding.NamedSharding(self.mesh, P())
        ef = {k: jax.device_put(v, sh if k == "blocks" else rep)
              for k, v in state.ef.items()}
        return state._replace(ef=ef)

    def feature_sum(self, h_fn: Callable, head_fn: Callable,
                    block_grad_fn: Callable, blocks, zb, *,
                    codec=None, ef=None, head_key=None, block_keys=None,
                    dp=None, dp_head_key=None, dp_block_keys=None,
                    dp_scale=1.0) -> FeatureSums:
        """Same contract as :meth:`LocalTopology.feature_sum`, with each
        shard running its I/D resident feature clients and the paper's
        step-4 h-broadcast realized as a tiled `lax.all_gather` over the
        client axes: every shard reassembles the FULL (I, B, J) h in
        canonical client order, so Σ_i h_i — and everything downstream of
        it (head gradient, dl/dh, block gradients, codec wire formats) —
        is bit-identical to the local reference. The head computation, its
        DP clip+noise, and its codec roundtrip are replicated per shard
        (same inputs, same keys → same bits); block gradients, their noise
        draws, and their EF residuals never leave their shard."""
        num_clients = jax.tree.leaves(blocks)[0].shape[0]
        self._check_divisible(num_clients)
        axes = self.axes
        spec = P(axes)
        has_codec = codec is not None
        has_dp = dp is not None
        ef_spec = ({"w0": P(), "blocks": spec}
                   if has_codec and ef is not None else P())
        keys_spec = spec if block_keys is not None else P()
        enc_spec = {"q_head": P(), "q_blocks": spec} if has_codec else P()
        ef_out_spec = {"w0": P(), "blocks": spec} if has_codec else P()
        dp_keys_spec = spec if dp_block_keys is not None else P()
        dp_out_spec = ({"head_clipped": P(), "head_noise_sq": P(),
                        "blocks_clipped": spec, "blocks_noise_sq": spec}
                       if has_dp else P())

        def body(blocks_l, zb_l, ef_l, bkeys_l, hkey, dpbk_l, dphk):
            with obs_trace.phase("client-compute"):
                h_l = jax.vmap(h_fn)(blocks_l, zb_l)         # (I/D, B, J)
            with obs_trace.phase("collective"):
                h_all = jax.lax.all_gather(h_l, axes, axis=0, tiled=True)
            with obs_trace.phase("aggregate"):
                h_sum = jnp.sum(h_all, axis=0)
            with obs_trace.phase("head-compute"):
                value, q_head, dl_dh = head_fn(h_sum)
            with obs_trace.phase("client-compute"):
                q_blocks = jax.vmap(block_grad_fn, in_axes=(0, 0, None))(
                    blocks_l, zb_l, dl_dh)
            enc = new_ef = dp_stats = None
            if has_dp:
                with obs_trace.phase("dp-privatize"):
                    q_head, q_blocks, dp_stats = _privatize_feature(
                        dp, q_head, q_blocks, dphk, dpbk_l, dp_scale)
            if has_codec:
                with obs_trace.phase("codec-encode"):
                    enc, q_head, q_blocks, new_ef = _compress_feature(
                        codec, q_head, q_blocks, ef_l, hkey, bkeys_l)
            return h_l, h_sum, value, q_head, q_blocks, enc, new_ef, dp_stats

        sharded = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(spec, spec, ef_spec, keys_spec, P(), dp_keys_spec, P()),
            out_specs=(spec, P(), P(), P(), spec, enc_spec, ef_out_spec,
                       dp_out_spec),
            check_vma=False)
        h, h_sum, value, q_head, q_blocks, enc, new_ef, dp_stats = sharded(
            blocks, zb, ef, block_keys, head_key, dp_block_keys, dp_head_key)
        return FeatureSums(h=h, h_sum=h_sum, value=value, q_head=q_head,
                           q_blocks=q_blocks, encoded=enc, ef=new_ef,
                           dp=dp_stats)


LOCAL = LocalTopology()


def make_topology(name: str, mesh=None, axes=None):
    """CLI-name -> topology. "local" ignores mesh; "sharded" uses the given
    mesh or builds a 1-D client mesh over all host devices
    (`launch.mesh.make_client_mesh`)."""
    if name == "local":
        return LOCAL
    if name == "sharded":
        if mesh is None:
            from repro.launch.mesh import make_client_mesh
            mesh = make_client_mesh()
        return ShardedTopology(mesh, axes=axes)
    raise ValueError(f"unknown topology {name!r} (choose local|sharded)")


def sharded_for(num_clients: int) -> ShardedTopology:
    """ShardedTopology over the MOST host devices that divide the client
    count — the one divisibility-fitting policy shared by the example
    sweeps and the adaptive tests (a 1-device fit still runs the
    shard_map + psum path, so callers need no special-casing)."""
    from repro.launch.mesh import make_client_mesh
    d = jax.device_count()
    while num_clients % d:
        d -= 1
    return ShardedTopology(make_client_mesh(d))


def feature_sharded_for(num_clients: int) -> ShardedTopology:
    """Feature-based analog of :func:`sharded_for`: the same best-divisor
    device fit, but over a "model"-axis mesh (DESIGN.md §2/§12 — feature
    clients ARE model shards; a 1-device fit still runs the shard_map +
    all_gather path)."""
    from repro.launch.mesh import make_feature_mesh
    d = jax.device_count()
    while num_clients % d:
        d -= 1
    return ShardedTopology(make_feature_mesh(d))
