"""Fused stochastic quantize-dequantize Pallas TPU kernel (DESIGN.md §10).

One VMEM pass per block computes, for each `chunk`-sized slice of the flat
upload vector: the absmax scale, the stochastically-rounded int levels, and
the dequantized reconstruction the error-feedback update needs:

    scale_c = max|x_c| / qmax
    v_c     = clip(floor(x_c/scale_c + u), -qmax, qmax)      u ~ U[0,1)
    xhat_c  = v_c · scale_c

Op-by-op XLA reads x once for the per-chunk max, again for the rounding,
and the int values again for the dequantize; the fused kernel reads x (and
the random bits) once and writes v/scales/xhat in the same pass — this is
the encode hot path of every compressed round (codecs.StochasticQuantizer
``impl="pallas"``).

Blocking follows kernels/ssca_update.py: the vector is reshaped to
(C, chunk) rows and blocked by `block_rows`; the padded tail rows are
all-zero (scale 0) and sliced away. Randomness comes either from a raw
uint32 `bits` operand — the portable path, bit-identical to the codecs.py
ref math and testable in interpret mode — or, with `bits=None`, from the
on-core PRNG seeded per block via scalar prefetch (TPU-only: interpret mode
has no prng_seed lowering).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.comm.codecs import uniform_from_bits

DEFAULT_BLOCK_ROWS = 128    # 128 rows x 256 lanes x (4+4+4+1)B ~ 0.4 MiB VMEM


def _qdq_kernel(sc_ref, x_ref, *rest, qmax: int, device_prng: bool):
    if device_prng:
        v_ref, s_ref, xh_ref = rest
        # multi-operand seed: (round seed, block) pairs never collide, unlike
        # seed + program_id where round t block b+1 == round t+1 block b
        pltpu.prng_seed(sc_ref[0], pl.program_id(0))
        bits = pltpu.bitcast(pltpu.prng_random_bits(x_ref.shape), jnp.uint32)
    else:
        bits_ref, v_ref, s_ref, xh_ref = rest
        bits = bits_ref[...]
    x = x_ref[...].astype(jnp.float32)
    u = uniform_from_bits(bits)     # single-sourced: codec ref == kernel
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)     # (rows, 1)
    # explicit reciprocal-multiply, matching codecs.stochastic_round_chunks
    # exactly (XLA strength-reduces /const inconsistently across contexts)
    scale = absmax * jnp.float32(1.0 / qmax)
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.floor(x / safe + u), -qmax, qmax)
    v_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale
    xh_ref[...] = q * scale


def stochastic_quantize_pallas(x, qmax: int, chunk: int = 256, *,
                               bits=None, seed=None,
                               block_rows: int = DEFAULT_BLOCK_ROWS,
                               interpret: bool = False):
    """x: any shape, flattened to (P,). Returns
    (values int8 (C·chunk,), scales fp32 (C,), xhat fp32 (P,)), C=ceil(P/chunk).

    bits: uint32 (C·chunk,) random bits (portable / interpret-testable);
    bits=None seeds the on-core PRNG from `seed` instead (TPU only) — the
    caller must then thread a fresh per-round seed, or every round reuses
    the same rounding noise and unbiased averaging breaks.
    """
    if bits is None and seed is None:
        raise ValueError("pass `bits` or a per-round `seed`: a fixed "
                         "device-PRNG seed repeats the rounding noise "
                         "every round")
    xf = x.reshape(-1).astype(jnp.float32)
    p = xf.shape[0]
    num_chunks = -(-p // chunk)
    rows = min(block_rows, num_chunks)
    padded_rows = -(-num_chunks // rows) * rows
    xc = jnp.pad(xf, (0, padded_rows * chunk - p)).reshape(padded_rows, chunk)

    device_prng = bits is None
    scalars = jnp.asarray([seed if device_prng else 0], jnp.int32)
    operands = [xc]
    in_specs = [pl.BlockSpec((rows, chunk), lambda i, sc: (i, 0))]
    if not device_prng:
        bc = jnp.pad(bits.reshape(-1), (0, padded_rows * chunk - bits.size))
        operands.append(bc.reshape(padded_rows, chunk))
        in_specs.append(pl.BlockSpec((rows, chunk), lambda i, sc: (i, 0)))

    v, s, xh = pl.pallas_call(
        functools.partial(_qdq_kernel, qmax=qmax, device_prng=device_prng),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(padded_rows // rows,),
            in_specs=in_specs,
            # scales are a (rows, 1) column, not a 1-D (rows,) block: under
            # vmap (one call per client) the block gains a leading squeezed
            # axis, and a 1-D block would then break the (8, 128) tiling rule
            out_specs=[pl.BlockSpec((rows, chunk), lambda i, sc: (i, 0)),
                       pl.BlockSpec((rows, 1), lambda i, sc: (i, 0)),
                       pl.BlockSpec((rows, chunk), lambda i, sc: (i, 0))],
        ),
        out_shape=[jax.ShapeDtypeStruct((padded_rows, chunk), jnp.int8),
                   jax.ShapeDtypeStruct((padded_rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((padded_rows, chunk), jnp.float32)],
        interpret=interpret,
    )(scalars, *operands)
    return (v.reshape(-1)[: num_chunks * chunk], s[:num_chunks, 0],
            xh.reshape(-1)[:p])
