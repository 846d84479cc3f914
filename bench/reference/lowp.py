"""Matrix products of the reference at full float32 precision, or one step
lower for the controls: ``"high"`` is XLA's three-pass bfloat16 product
(on the TPU; other backends may compute it in full float32), and
``"bf16"`` rounds each operand (and, backwards, the cotangent) to bfloat16
and accumulates in float32, as one bfloat16 pass does on any backend."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def _mm16(a, b):
    return jnp.matmul(_bf16(a), _bf16(b), precision=HI)


def _mm16_fwd(a, b):
    return _mm16(a, b), (a, b)


def _mm16_bwd(res, g):
    a, b = res
    g16, a16, b16 = _bf16(g), _bf16(a), _bf16(b)
    da = jnp.matmul(g16, jnp.swapaxes(b16, -1, -2), precision=HI)
    db = jnp.matmul(jnp.swapaxes(a16, -1, -2), g16, precision=HI)
    while da.ndim > a.ndim:          # batch axes broadcast over a
        da = da.sum(0)
    while db.ndim > b.ndim:          # batch axes broadcast over b
        db = db.sum(0)
    return da, db


_mm16.defvjp(_mm16_fwd, _mm16_bwd)


def matmul(a, b, precision: str):
    """a @ b in float32 ("f32"), three bfloat16 passes ("high") or from
    bfloat16 operands ("bf16")."""
    if precision == "bf16":
        return _mm16(a, b)
    if precision == "high":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    if precision != "f32":
        raise ValueError(f"precision {precision!r}: f32, high or bf16")
    return jnp.matmul(a, b, precision=HI)
