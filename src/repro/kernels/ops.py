"""Jit'd public wrappers for the Pallas kernels with ref fallback.

On the TPU target the Pallas path compiles natively; in this CPU container
kernels execute via ``interpret=True`` (Python emulation of the kernel body),
which is what the per-kernel allclose tests sweep. ``use_pallas(False)`` (or
running on a CPU backend without interpret) falls back to the jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssca_update import ssca_update_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("eps", "impl"))
def rmsnorm(x, scale, eps: float = 1e-6, impl: str = "auto"):
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.rmsnorm_ref(x, scale, eps)
    return rmsnorm_pallas(x, scale, eps, interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("tau", "lam", "impl"))
def ssca_update(w, buf, grad, rho, gamma, tau: float, lam: float,
                impl: str = "auto"):
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.ssca_update_ref(w, buf, grad, rho, gamma, tau, lam)
    return ssca_update_pallas(w, buf, grad, rho, gamma, tau, lam,
                              interpret=(impl == "interpret"))
