"""Baseline FL algorithms the paper compares against (§VI):

  - sample-based SGD  [5],[6]: E local SGD steps per round, weighted model
    averaging (E=1 & full batch -> FedSGD; B·E = N_i -> FedAvg; E>1 -> PR-SGD)
  - sample-based SGD-m [7]: E local momentum-SGD steps, constant stepsize
  - feature-based SGD / SGD-m [13]: one global step per round using the same
    h-exchange information collection as Algorithm 3

Learning rates follow §VI: SGD r_t = ā/t^ᾱ; SGD-m constant ā, momentum β̄.

Both baselines take ``codec=`` (repro.comm) so the compression comparison is
apples-to-apples with the SSCA drivers: sample-based SGD compresses each
client's *model delta* Δ_i = ω_i^local − ω (the round's upload; the weighted
average Σ w_i(ω + Δ̂_i) = ω + Σ w_i Δ̂_i since Σ w_i = 1), feature-based SGD
compresses the same q-uploads as Algorithm 3 via ``fed.feature_round``.
Error-feedback residuals ride the scan carry in a CommCarry, exactly as in
core/algorithms.py.

``sample_sgd`` also takes ``topology=`` (core/topology.py): its per-client
local-step loop + delta upload + N_i/N weighted averaging run through the
same client-execution engine as the SSCA drivers, so the baseline comparison
stays apples-to-apples on a sharded mesh too.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.comm import accounting as comm_accounting
from repro.comm import codecs as comm_codecs
from repro.comm import error_feedback as comm_ef
from repro.comm.error_feedback import with_comm_carry
from repro.core import fed
from repro.core import topology as topology_lib
from repro.core.algorithms import (RunResult, _check_cohort,
                                   _feature_axis_bytes, _feature_ef0,
                                   _feature_upload_bytes, _run, _run_feature,
                                   _wrap_codec_state)
from repro.core.fed import FeatureFedData, SampleFedData
from repro.core.tree import tree_axpy, tree_l2sq, tree_zeros_like
from repro.obs import trace as obs_trace


class SGDConfig(NamedTuple):
    lr_a: float = 0.3          # ā
    lr_alpha: float = 0.3      # ᾱ  (0 -> constant stepsize)
    momentum: float = 0.0      # β̄ (SGD-m)
    local_steps: int = 1       # E
    local_batch: int = 10      # per-local-step batch size
    l2_lambda: float = 1e-5


def _lr(cfg: SGDConfig, t):
    t = jnp.maximum(t, 1).astype(jnp.float32)
    return cfg.lr_a / t**cfg.lr_alpha


class SGDState(NamedTuple):
    params: object
    t: jnp.ndarray


class SGDmState(NamedTuple):
    params: object
    v: object
    t: jnp.ndarray


def _reg_grad(per_sample_loss, lam):
    def f(p, z, y):
        return jnp.mean(per_sample_loss(p, z, y)) + lam * tree_l2sq(p)
    return jax.grad(f)


def sample_sgd(per_sample_loss, params0, data: SampleFedData, cfg: SGDConfig,
               rounds: int, key, eval_fn=None, eval_every: int = 10,
               momentum: bool = False, codec=None, topology=None,
               obs=None, participation=None, cohort: bool = False) -> RunResult:
    """E local (momentum-)SGD steps per client per round + weighted averaging.
    Each client's upload is its model delta Δ_i = ω_i^local − ω (compressed
    when a codec is given); the server applies ω ← ω + Σ_i (N_i/N) Δ̂_i,
    which equals weighted model averaging because Σ_i w_i = 1. The
    client-local steps + delta uploads + weighted sum run through the
    topology engine (core/topology.py), so ``topology=sharded`` distributes
    the E local steps of each client over the mesh like the SSCA drivers.

    ``participation=S`` draws S-of-I clients per round (`fed.cohort_sample`
    under the dense mask), Horvitz-Thompson reweighting the delta average:
    ω ← ω + (I/S)·Σ_{i∈cohort} (N_i/N) Δ̂_i — unbiased for the full-
    participation update since E over cohorts recovers every w_i.
    ``cohort=True`` additionally switches to the participant-only O(S)
    engine (DESIGN.md §14): only the cohort's shards are gathered (or
    generated, for a `VirtualFedData`), EF residuals live in a keyed
    `EFStore`, and the dense trajectory is reproduced to float
    reassociation on the same keys."""
    grad_fn = _reg_grad(per_sample_loss, cfg.l2_lambda)
    topo = topology if topology is not None else topology_lib.LOCAL
    _check_cohort("sample_sgd", cohort, participation)
    num_clients = data.num_clients
    dim = comm_codecs.tree_flat_dim(params0)
    up_bytes = float(comm_accounting.sample_round_bytes(
        dim, num_clients, codec, participation=participation)["up"])

    def local(params_v0, feat_i, lab_i, count_i, k, lr):
        def one(step, carry):
            p, v = carry
            kk = jax.random.fold_in(k, step)
            idx = jax.random.randint(kk, (cfg.local_batch,), 0, count_i)
            g = grad_fn(p, jnp.take(feat_i, idx, 0), jnp.take(lab_i, idx, 0))
            if momentum:
                v = jax.tree.map(lambda vv, gg: cfg.momentum * vv + gg, v, g)
                upd = v
            else:
                upd = g
            p = jax.tree.map(lambda pp, uu: pp - lr * uu, p, upd)
            return p, v

        v0 = tree_zeros_like(params_v0)
        return jax.lax.fori_loop(0, cfg.local_steps, one, (params_v0, v0))

    def body(state, inp, ef):
        lr = cfg.lr_a if momentum else _lr(cfg, state.t)

        def client_fn(f_, l_, c_, k_):
            p_local, _ = local(state.params, f_, l_, c_, k_, lr)
            delta = jax.tree.map(lambda u, p: u - p, p_local, state.params)
            return delta, jnp.zeros((), jnp.float32)

        ck = jax.random.fold_in(inp.key, 0xC0DEC)
        if cohort:
            pk = jax.random.fold_in(inp.key, 0x5ca)
            ids = fed.cohort_sample(pk, num_clients, participation)
            feats, labs, counts_s = data.shards_for(ids)
            keys = fed.client_keys(inp.key, ids)
            w = ((num_clients / participation)
                 * counts_s.astype(jnp.float32) / data.total)
            ckeys = fed.client_keys(ck, ids) if codec is not None else None
            ef_rows = None
            if codec is not None and ef is not None:
                with obs_trace.phase("ef-gather"):
                    ef_rows = ef.gather(ids, topo.mesh)
            s = topo.weighted_sum(client_fn, (feats, labs, counts_s, keys), w,
                                  codec=codec, ef=ef_rows, codec_keys=ckeys)
            new_ef = s.ef
            if ef_rows is not None:
                with obs_trace.phase("ef-scatter"):
                    new_ef = ef.scatter(ids, s.ef, topo.mesh)
        else:
            keys = fed.client_keys(inp.key, jnp.arange(num_clients))
            w = data.counts.astype(jnp.float32) / jnp.sum(data.counts)
            active = None
            if participation is not None and participation < num_clients:
                pmask = fed.participation_mask(
                    jax.random.fold_in(inp.key, 0x5ca), num_clients,
                    participation)
                w = w * pmask * (num_clients / jnp.sum(pmask))
                active = pmask
            ckeys = (fed.client_keys(ck, jnp.arange(num_clients))
                     if codec is not None else None)
            s = topo.weighted_sum(
                client_fn, (data.features, data.labels, data.counts, keys),
                w, codec=codec, ef=ef, codec_keys=ckeys, active=active)
            new_ef = s.ef
        params = jax.tree.map(lambda p, d: (p + d).astype(p.dtype),
                              state.params, s.weighted)
        new = SGDState(params=params, t=state.t + 1)
        return new, new_ef, {"upload_bytes": up_bytes}

    state = _wrap_codec_state(
        SGDState(params=params0, t=jnp.ones((), jnp.int32)), codec,
        lambda: (comm_ef.ef_store_init(num_clients, dim) if cohort
                 else comm_ef.ef_init_stacked(num_clients, dim)))
    return _run(with_comm_carry(codec, body), state, key, rounds, eval_fn,
                eval_every, topology=topology, obs=obs)


def feature_sgd(head_loss_from_h, client_h, params0, data: FeatureFedData,
                cfg: SGDConfig, rounds: int, key, eval_fn=None,
                eval_every: int = 10, momentum: bool = False,
                codec=None, topology=None, obs=None) -> RunResult:
    """One global (momentum-)SGD step per round via the Alg-3 info collection
    (codec compresses the same q-uploads as Algorithm 3; topology runs the
    feature clients local or model-axis sharded, DESIGN.md §12)."""
    def body(state, inp, ef):
        if momentum:
            params, v, t = state.params, state.v, state.t
        else:
            params, t = state.params, state.t
        grad_est, _, up = fed.feature_round(
            params, data, inp.key, cfg.local_batch, head_loss_from_h,
            client_h, codec=codec, ef=ef, topology=topology)
        grad_est = jax.tree.map(
            lambda g, p: g + 2 * cfg.l2_lambda * p, grad_est, params)
        lr = cfg.lr_a if momentum else _lr(cfg, t)
        if momentum:
            v = jax.tree.map(lambda vv, gg: cfg.momentum * vv + gg, v, grad_est)
            params = jax.tree.map(lambda p, u: p - lr * u, params, v)
            new = SGDmState(params=params, v=v, t=t + 1)
        else:
            params = jax.tree.map(lambda p, g: p - lr * g, params, grad_est)
            new = SGDState(params=params, t=t + 1)
        metrics = {"upload_bytes": _feature_upload_bytes(
            up, grad_est, data, cfg.local_batch)}
        return new, up["ef"], metrics

    if momentum:
        state = SGDmState(params=params0, v=tree_zeros_like(params0),
                          t=jnp.ones((), jnp.int32))
    else:
        state = SGDState(params=params0, t=jnp.ones((), jnp.int32))
    state = _wrap_codec_state(
        state, codec, lambda: _feature_ef0(params0, data.num_clients))
    return _run_feature(with_comm_carry(codec, body), state, key, rounds,
                        eval_fn, eval_every, topology=topology, obs=obs)


# ---------------------------------------------------------------------------
# constrained vertical-FL baselines (benchmarks/feature_bench.py scenario:
# min ‖ω‖² s.t. F(ω) <= U, the paper's formulation (40) under the Alg-3/4
# feature composition) — both collect the exact same per-round information
# as Algorithm 4 (fed.feature_round: h-exchange + head/block q-uploads), so
# rounds and upload bytes are apples-to-apples; only the update rule differs.
# ---------------------------------------------------------------------------


class FWConfig(NamedTuple):
    """Projection-free federated Frank-Wolfe baseline (after Dadras et al.,
    *Federated Frank-Wolfe Algorithm*): exact-penalty reformulation
    min_{‖ω‖<=R} ‖ω‖² + c·max(0, F̂(ω) − U) over an L2 ball, linear
    minimization oracle s = −R·g/‖g‖, classic step η_t = a/(t+2)."""
    radius: float = 10.0       # feasible-ball radius R (the LMO domain)
    penalty: float = 10.0      # exact-penalty weight c on the hinge
    lr_a: float = 2.0          # η_t = lr_a/(t+2)


def feature_frank_wolfe(head_loss_from_h, client_h, params0,
                        data: FeatureFedData, fl, cfg: FWConfig, rounds: int,
                        key, eval_fn=None, eval_every: int = 10,
                        driver: str = "scan", codec=None,
                        topology=None, obs=None) -> RunResult:
    """ω_{t+1} = (1−η_t)ω_t + η_t·s_t with s_t the L2-ball LMO of the
    penalized subgradient g_t = 2ω_t + c·1[F̂>U]·∇F̂(ω_t). The iterate stays
    inside the ball by convexity, so the method is projection-free; it has
    no dual iterate, so feature_bench scores its KKT stationarity at the
    best-response multiplier (solvers.kkt_best_nu)."""
    def body(state, inp, ef):
        grad_est, val_est, up = fed.feature_round(
            state.params, data, inp.key, fl.batch_size, head_loss_from_h,
            client_h, codec=codec, ef=ef, topology=topology)
        act = (val_est > fl.cost_limit).astype(jnp.float32)
        g = jax.tree.map(lambda p, gf: 2.0 * p + cfg.penalty * act * gf,
                         state.params, grad_est)
        norm = jnp.sqrt(jnp.maximum(tree_l2sq(g), 1e-24))
        s_lmo = jax.tree.map(lambda gg: -cfg.radius * gg / norm, g)
        eta = cfg.lr_a / (state.t.astype(jnp.float32) + 2.0)
        params = jax.tree.map(
            lambda p, s_: ((1.0 - eta) * p + eta * s_).astype(p.dtype),
            state.params, s_lmo)
        new = SGDState(params=params, t=state.t + 1)
        metrics = {"loss_est": val_est,
                   "upload_bytes": _feature_upload_bytes(
                       up, grad_est, data, fl.batch_size),
                   "axis_bytes": _feature_axis_bytes(topology, up)}
        return new, up["ef"], metrics

    state = _wrap_codec_state(
        SGDState(params=params0, t=jnp.ones((), jnp.int32)), codec,
        lambda: _feature_ef0(params0, data.num_clients))
    return _run_feature(with_comm_carry(codec, body), state, key, rounds,
                        eval_fn, eval_every, fl=fl, driver=driver,
                        topology=topology, obs=obs)


class DualConfig(NamedTuple):
    """Dual-decomposition / Arrow-Hurwicz baseline (after Fan et al., *A dual
    approach for federated learning*): alternating primal descent on the
    Lagrangian L(ω,ν) = ‖ω‖² + ν(F̂(ω) − U) and projected dual ascent, both
    with diminishing a/√t stepsizes."""
    lr_primal: float = 0.2
    lr_dual: float = 1.0
    nu_max: float = 1e4        # dual cap, mirrors the SSCA penalty_c role


class DualState(NamedTuple):
    params: object
    nu: jnp.ndarray
    t: jnp.ndarray


def feature_dual_decomposition(head_loss_from_h, client_h, params0,
                               data: FeatureFedData, fl, cfg: DualConfig,
                               rounds: int, key, eval_fn=None,
                               eval_every: int = 10, driver: str = "scan",
                               codec=None, topology=None, obs=None) -> RunResult:
    """ω ← ω − η_ω(2ω + ν∇F̂);  ν ← clip(ν + η_ν(F̂ − U), 0, ν_max). Its ν
    IS a dual iterate, so feature_bench scores its KKT residuals directly."""
    def body(state, inp, ef):
        grad_est, val_est, up = fed.feature_round(
            state.params, data, inp.key, fl.batch_size, head_loss_from_h,
            client_h, codec=codec, ef=ef, topology=topology)
        sqrt_t = jnp.sqrt(state.t.astype(jnp.float32))
        lag = jax.tree.map(lambda p, gf: 2.0 * p + state.nu * gf,
                           state.params, grad_est)
        params = tree_axpy(1.0, state.params, -cfg.lr_primal / sqrt_t, lag)
        params = jax.tree.map(lambda p, p0: p.astype(p0.dtype), params,
                              state.params)
        nu = jnp.clip(state.nu + (cfg.lr_dual / sqrt_t)
                      * (val_est - fl.cost_limit), 0.0, cfg.nu_max)
        new = DualState(params=params, nu=nu, t=state.t + 1)
        metrics = {"loss_est": val_est, "nu": nu,
                   "upload_bytes": _feature_upload_bytes(
                       up, grad_est, data, fl.batch_size),
                   "axis_bytes": _feature_axis_bytes(topology, up)}
        return new, up["ef"], metrics

    state = _wrap_codec_state(
        DualState(params=params0, nu=jnp.zeros((), jnp.float32),
                  t=jnp.ones((), jnp.int32)), codec,
        lambda: _feature_ef0(params0, data.num_clients))
    return _run_feature(with_comm_carry(codec, body), state, key, rounds,
                        eval_fn, eval_every, fl=fl, driver=driver,
                        topology=topology, obs=obs)
