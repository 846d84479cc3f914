"""SSCA stepsize schedules (paper eqs. (4) and (6)).

rho^(t) = a1 / t**alpha_rho   — surrogate averaging weight, must satisfy (4):
    0 < rho <= 1,  rho -> 0,  sum rho = inf.
gamma^(t) = a2 / t**alpha_gamma — iterate stepsize, must satisfy (6):
    0 < gamma <= 1, gamma -> 0, sum gamma = inf, sum gamma^2 < inf,
    gamma/rho -> 0.

The paper's own grid-searched settings use alpha_gamma == alpha_rho (= 0.1/0.3),
which satisfies (4) but not the last two conditions of (6) in the strict limit —
they hold on any finite horizon and work empirically (paper §VI). We default to
a theory-compliant alpha_gamma = 0.6 and expose the paper's values in configs.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def _power_law(a, t, alpha):
    t = jnp.maximum(t, 1).astype(jnp.float32)
    # the barrier keeps a compiled program from rewriting a / t**alpha as
    # a * t**(-alpha), which rounds differently: with alpha passed in as a
    # traced value, the compiled schedule equals the eager one bit for bit
    return jnp.minimum(a / lax.optimization_barrier(t**alpha), 1.0)


def rho(t, a1: float, alpha: float):
    """t is 1-based. Returns rho^(t) clipped to (0, 1]."""
    return _power_law(a1, t, alpha)


def gamma(t, a2: float, alpha: float):
    return _power_law(a2, t, alpha)


def check_conditions(a1, a2, alpha_rho, alpha_gamma, strict=True):
    """Static sanity check of (4)/(6). Returns list of violations."""
    bad = []
    if not (0 < a1 <= 1 or alpha_rho > 0):
        bad.append("rho(1) must be in (0,1]")
    if alpha_rho <= 0 or alpha_rho > 1:
        bad.append("need 0 < alpha_rho <= 1 for rho->0 and sum rho = inf")
    if alpha_gamma <= 0 or alpha_gamma > 1:
        bad.append("need 0 < alpha_gamma <= 1 for gamma->0 and sum gamma = inf")
    if strict:
        if 2 * alpha_gamma <= 1:
            bad.append("sum gamma^2 < inf requires alpha_gamma > 0.5")
        if alpha_gamma <= alpha_rho:
            bad.append("gamma/rho -> 0 requires alpha_gamma > alpha_rho")
    return bad
