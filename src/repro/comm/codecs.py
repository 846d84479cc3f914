"""Upload codecs: lossy compressors for the q-statistics that cross the
client boundary (DESIGN.md §10).

Every codec implements the same three-method protocol

    encode(x, key)  -> Encoded        x: (P,) fp32 flat upload vector
    decode(enc, p)  -> x_hat (P,)     server-side reconstruction
    nbytes(p)       -> int            exact wire bytes for a P-vector (static)

(`key` may be None only for deterministic codecs — Identity, TopK;
stochastic quantizers raise without one, since reused rounding noise would
break unbiasedness.)

plus ``roundtrip(x, key) -> (enc, x_hat)`` (fused where the backend allows).
Codecs are frozen dataclasses — hashable static configuration captured in
step closures, so a scan-compiled round chain traces once per codec. All
encode/decode bodies are pure jnp with static shapes: they vmap over clients
and ride inside ``lax.scan`` without retracing.

Quantizers use *stochastic rounding*, which is unbiased:
E[decode(encode(x))] = x exactly (per-chunk absmax scaling never clips), so
the SSCA gradient estimate stays unbiased and Theorem 1's convergence
argument applies with inflated variance. Top-k is biased; pair it with
``error_feedback.ef_roundtrip`` so the bias is re-injected next round.

The uniform noise is derived from raw PRNG bits via ``uniform_from_bits`` —
the same formula the Pallas kernel (kernels/quantize.py) applies to its bits
operand, so the ``impl="pallas"`` path matches ``impl="ref"`` bit-for-bit.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

F32_BYTES = 4
IDX_BYTES = 4      # int32 coordinate per kept entry (top-k wire format)


# ---------------------------------------------------------------------------
# shared quantization math (also the oracle for kernels/quantize.py)
# ---------------------------------------------------------------------------


def uniform_from_bits(bits):
    """uint32 random bits -> Uniform[0,1) with 24-bit mantissa precision.
    Identical to the Pallas kernel's formula so ref == kernel exactly. The
    top 24 bits fit an int32, so converting through int32 is exact — and it
    is the integer->float conversion Mosaic lowers (it refuses uint32)."""
    return ((bits >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))


def chunk_pad(x, chunk: int):
    """(P,) -> (C, chunk) zero-padded, C = ceil(P/chunk)."""
    p = x.shape[0]
    pad = (-p) % chunk
    return jnp.pad(x.astype(jnp.float32), (0, pad)).reshape(-1, chunk)


def stochastic_round_chunks(xc, u, qmax: int):
    """Per-chunk absmax scale + stochastic rounding. xc, u: (C, chunk).
    Returns (q int8 (C, chunk), scales fp32 (C,)). Unbiased:
    E[floor(y+u)] = y for u ~ U[0,1), and |y| <= qmax up to one ulp of the
    scale, which the safety clip absorbs. The scale is an explicit
    reciprocal-multiply (not absmax/qmax) so XLA computes the identical op
    in every compilation context — division by a constant gets
    strength-reduced to a one-ulp-different multiply only sometimes, which
    would break the exact codec == Pallas-kernel parity."""
    absmax = jnp.max(jnp.abs(xc), axis=1, keepdims=True)
    scale = absmax * jnp.float32(1.0 / qmax)
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.floor(xc / safe + u), -qmax, qmax)
    return q.astype(jnp.int8), scale[:, 0]


# ---------------------------------------------------------------------------
# encoded wire formats (pytrees of arrays — scan/vmap transparent)
# ---------------------------------------------------------------------------


class DenseEncoded(NamedTuple):
    values: jnp.ndarray            # (P,) fp32


class QuantEncoded(NamedTuple):
    values: jnp.ndarray            # (C*chunk,) int8 (int4 packs at wire level)
    scales: jnp.ndarray            # (C,) fp32 per-chunk scales


class TopKEncoded(NamedTuple):
    values: jnp.ndarray            # (k,) fp32 kept entries
    indices: jnp.ndarray           # (k,) int32 coordinates


class ChainEncoded(NamedTuple):
    indices: jnp.ndarray           # (k,) int32 coordinates
    inner: QuantEncoded            # quantized kept values


@runtime_checkable
class Codec(Protocol):
    def encode(self, x, key=None): ...
    def decode(self, enc, p: int): ...
    def nbytes(self, p: int) -> int: ...
    def roundtrip(self, x, key=None): ...


class _CodecBase:
    def roundtrip(self, x, key=None):
        """encode + decode in one call; backends may fuse (see
        StochasticQuantizer's pallas path)."""
        enc = self.encode(x, key)
        return enc, self.decode(enc, x.shape[0])


@dataclass(frozen=True)
class Identity(_CodecBase):
    """Dense fp32 passthrough — the uncompressed baseline, and the codec that
    makes `codec=` wiring exactly equal to the no-codec path."""

    def encode(self, x, key=None):
        return DenseEncoded(values=x)

    def decode(self, enc, p: int):
        return enc.values

    def nbytes(self, p: int) -> int:
        return F32_BYTES * p


@dataclass(frozen=True)
class StochasticQuantizer(_CodecBase):
    """Unbiased b-bit quantizer with per-chunk fp32 absmax scales.

    bits=8 -> levels [-127, 127] (1 byte/entry on the wire); bits=4 ->
    [-7, 7] (half a byte — the simulation stores int8 and the accounting
    charges bits/8, packing being a wire-format detail). impl="pallas" runs
    the fused quantize-dequantize kernel (kernels/quantize.py) on the padded
    chunks; it consumes the same PRNG bits as the ref path, so both impls
    produce identical wire values.
    """
    bits: int = 8
    chunk: int = 256
    impl: str = "ref"              # ref | pallas
    interpret: bool = False        # pallas interpret mode (CPU testing)

    @property
    def qmax(self) -> int:
        return (1 << (self.bits - 1)) - 1

    def _bits(self, key, num_chunks: int):
        if key is None:
            raise ValueError(
                "StochasticQuantizer needs a PRNG key: rounding noise must "
                "be fresh per encode or E[decode(encode(x))] = x fails "
                "(deterministic codecs like Identity/TopK accept key=None)")
        return jax.random.bits(key, (num_chunks, self.chunk), jnp.uint32)

    def encode(self, x, key=None):
        return self.roundtrip(x, key)[0]

    def roundtrip(self, x, key=None):
        p = x.shape[0]
        xc = chunk_pad(x, self.chunk)
        bits = self._bits(key, xc.shape[0])
        if self.impl == "pallas":
            from repro.kernels.quantize import stochastic_quantize_pallas
            v, s, xhat = stochastic_quantize_pallas(
                x, self.qmax, self.chunk, bits=bits.reshape(-1),
                interpret=self.interpret)
            return QuantEncoded(values=v, scales=s), xhat[:p]
        q, scales = stochastic_round_chunks(xc, uniform_from_bits(bits),
                                            self.qmax)
        enc = QuantEncoded(values=q.reshape(-1), scales=scales)
        return enc, self.decode(enc, p)

    def decode(self, enc, p: int):
        xc = (enc.values.astype(jnp.float32).reshape(-1, self.chunk)
              * enc.scales[:, None])
        return xc.reshape(-1)[:p]

    def nbytes(self, p: int) -> int:
        num_chunks = -(-p // self.chunk)
        return num_chunks * F32_BYTES + math.ceil(p * self.bits / 8)


@dataclass(frozen=True)
class TopK(_CodecBase):
    """Magnitude top-k sparsification: keep k = max(1, round(frac·P)) entries
    as (fp32 value, int32 index) pairs. Biased (E[decode] != x) — always run
    it behind error feedback; frac=1 recovers the dense vector exactly."""
    frac: float = 0.01

    def k(self, p: int) -> int:
        return max(1, min(p, int(round(self.frac * p))))

    def encode(self, x, key=None):
        _, idx = jax.lax.top_k(jnp.abs(x), self.k(x.shape[0]))
        idx = idx.astype(jnp.int32)
        return TopKEncoded(values=jnp.take(x, idx), indices=idx)

    def decode(self, enc, p: int):
        return (jnp.zeros((p,), jnp.float32)
                .at[enc.indices].set(enc.values.astype(jnp.float32)))

    def nbytes(self, p: int) -> int:
        return self.k(p) * (F32_BYTES + IDX_BYTES)


@dataclass(frozen=True)
class Chain(_CodecBase):
    """Composed codec: top-k sparsify, then quantize the kept values — the
    protocol composes, so sparsification's (k,) vector is just another
    upload for the quantizer."""
    sparse: TopK = field(default_factory=TopK)
    quant: StochasticQuantizer = field(default_factory=StochasticQuantizer)

    def encode(self, x, key=None):
        s = self.sparse.encode(x)
        return ChainEncoded(indices=s.indices,
                            inner=self.quant.encode(s.values, key))

    def decode(self, enc, p: int):
        vals = self.quant.decode(enc.inner, self.sparse.k(p))
        return jnp.zeros((p,), jnp.float32).at[enc.indices].set(vals)

    def nbytes(self, p: int) -> int:
        k = self.sparse.k(p)
        return k * IDX_BYTES + self.quant.nbytes(k)


def make_codec(name, topk_frac: float = 0.01, chunk: int = 256,
               impl: str = "ref"):
    """CLI-name -> codec instance; "none"/None -> None (dense fp32 path)."""
    if name is None or name == "none":
        return None
    if name == "identity":
        codec = Identity()
    elif name == "int8":
        codec = StochasticQuantizer(bits=8, chunk=chunk, impl=impl)
    elif name == "int4":
        codec = StochasticQuantizer(bits=4, chunk=chunk, impl=impl)
    elif name == "topk":
        codec = TopK(frac=topk_frac)
    elif name == "topk8":
        codec = Chain(sparse=TopK(frac=topk_frac),
                      quant=StochasticQuantizer(bits=8, chunk=chunk,
                                                impl=impl))
    else:
        raise ValueError(f"unknown codec {name!r} "
                         "(choose none|identity|int8|int4|topk|topk8)")
    # remember the CLI name for run manifests (obs/sinks.run_manifest);
    # frozen dataclass, so set through object.__setattr__
    object.__setattr__(codec, "name", name)
    return codec


# ---------------------------------------------------------------------------
# pytree <-> flat-vector adapters (static shapes; jit/vmap/scan safe)
# ---------------------------------------------------------------------------


def tree_flat_dim(tree, stacked: bool = False) -> int:
    """Total scalar count of a pytree; with stacked=True, per-client count of
    a tree whose leaves carry a leading client axis."""
    leaves = jax.tree.leaves(tree)
    total = sum(l.size for l in leaves)
    return total // leaves[0].shape[0] if stacked else total


def flatten_tree(tree):
    """pytree -> ((P,) fp32 flat vector, unflatten) with P static."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])

    def unflatten(f):
        out, o = [], 0
        for s, dt in zip(shapes, dtypes):
            n = math.prod(s)
            out.append(f[o:o + n].reshape(s).astype(dt))
            o += n
        return jax.tree.unflatten(treedef, out)

    return flat, unflatten


def flatten_stacked(tree):
    """pytree of (I, ...) leaves -> ((I, P) fp32, unflatten): one flat upload
    vector per client, so codecs vmap over the client axis."""
    leaves, treedef = jax.tree.flatten(tree)
    num = leaves[0].shape[0]
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    flat = jnp.concatenate(
        [l.reshape(num, -1).astype(jnp.float32) for l in leaves], axis=1)

    def unflatten(f):
        out, o = [], 0
        for s, dt in zip(shapes, dtypes):
            n = math.prod(s[1:])
            out.append(f[:, o:o + n].reshape(s).astype(dt))
            o += n
        return jax.tree.unflatten(treedef, out)

    return flat, unflatten
