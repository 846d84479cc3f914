"""Drivers for the paper's Algorithms 1-4 (faithful protocol simulation).

Each driver runs the paper's communication rounds with per-round client
mini-batch selection (PRNG-folded), the exact uploads of the paper, and the
closed-form server updates. The whole round chain is scan-compiled by
``core/rounds.py`` — a K-round run (or eval chunk) is a single XLA dispatch
with ρ^t/γ^t threaded through the scan (DESIGN.md §6).

The sample-based drivers (Algorithms 1/2) take ``participation=S`` to sample
S of I clients uniformly per round, with the unbiased I/S-reweighted
N_i/(B_i·N) aggregation of `fed.aggregation_weights`; they accept ragged
(e.g. Dirichlet-partitioned) client datasets transparently. Adding
``cohort=True`` switches the round body to the participant-only O(S) engine
(`fed.cohort_round`, DESIGN.md §14): per-round compute, uploads, and EF
state scale with S instead of the population I (residuals live in a keyed
`EFStore`, data may be a `data.synthetic.VirtualFedData` so I = 1e6 never
materializes), with the dense path's trajectory reproduced to float
reassociation (atol 1e-5) on the same keys.

Every driver takes ``codec=`` (repro.comm): q-uploads then cross the client
boundary in the codec's wire format, per-client error-feedback residuals
ride through the scan carry in a ``CommCarry`` wrapper, and each round's
metrics gain ``upload_bytes`` — the exact bytes-on-wire of that round's
uplink (repro.comm.accounting), so history["round_upload_bytes"] is the
Fig.-3 x-axis measured, not asserted.

The sample-based drivers also take ``topology=`` (core/topology.py,
DESIGN.md §11): `LocalTopology` (default) vmaps every client on one device;
`ShardedTopology` distributes clients over the mesh's client axes via
shard_map with the q-aggregation as a weighted psum — same trajectories up
to float reassociation, one scan dispatch spanning D devices. Under a
sharded topology the metrics additionally carry ``axis_bytes``, the
per-round bytes the aggregation psum moves over the client mesh axis
(repro.comm.accounting.psum_axis_bytes).

Every driver also takes ``dp=`` (repro.core.privacy.DPConfig, DESIGN.md
§15): client q-uploads are then clipped and Gaussian-noised at the client
boundary BEFORE any codec encode, and each round's metrics gain
``dp_epsilon`` (the subsampled-RDP accountant's ε spent through round t —
cross-round composition, in-graph via RoundInputs.t), ``dp_clip_frac``
(fraction of participating clients whose upload hit the clip norm), and
``dp_noise_norm`` (ℓ2 norm of the injected noise). Partial participation
(``participation=S`` / the cohort engine) is accounted with the q = S/I
subsampling amplification.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.comm import accounting as comm_accounting
from repro.comm import codecs as comm_codecs
from repro.comm.error_feedback import (CommCarry, ef_init, ef_init_stacked,
                                       ef_store_init, with_comm_carry)
from repro.core import fed, optimizer
from repro.core import privacy as privacy_lib
from repro.core import rounds as rounds_lib
from repro.core.fed import FeatureFedData, SampleFedData
from repro.core.rounds import RunResult  # re-exported (public API since seed)
from repro.obs import trace as obs_trace


def _run(step_fn, state, key, num_rounds: int, eval_fn: Optional[Callable],
         eval_every: int, extract_params=None, fl=None, driver: str = "scan",
         topology=None, obs=None):
    """Back-compat driver shim shared with baselines/local_updates: step_fn
    has the rounds.py signature step(state, RoundInputs-slice) -> (state,
    metrics). fl is only needed for the schedule inputs; steps that ignore
    rho/gamma (SGD baselines) may pass fl=None. extract_params=None uses the
    CommCarry-aware default (rounds.unwrap_comm). topology is forwarded so
    run_rounds can pre-place per-client carry state on the mesh; obs
    (repro.obs.MetricStream) streams each round's metrics while the scan
    runs."""
    fl = fl if fl is not None else _NULL_SCHED
    return rounds_lib.run_rounds(step_fn, state, fl, key, num_rounds,
                             eval_fn=eval_fn, eval_every=eval_every,
                             extract_params=extract_params, driver=driver,
                             topology=topology, obs=obs)


def _axis_bytes_metric(topology, grad_est, with_value: bool = False,
                       num_streams: int = 1):
    """Static per-round bytes over the client mesh axis (0.0 for local):
    the psum realization of the eq.-(9) aggregation moves pre-weighted
    partial sums, accounted once per driver here. grad_est only supplies
    the (trace-time static) flat dimension."""
    shards = getattr(topology, "num_shards", 1) if topology is not None else 1
    return float(comm_accounting.psum_axis_bytes(
        comm_codecs.tree_flat_dim(grad_est), shards, with_value=with_value,
        num_streams=num_streams))


def _sample_upload_bytes(uploads, grad_est, data, participation,
                         with_value: bool = False):
    """Static per-round uplink bytes metric: with a codec, fed.sample_round
    already computed the exact wire bytes (uploads["upload_nbytes"]) — reuse
    it so accounting has ONE call site per round; the dense path derives the
    fp32 bytes from the (trace-time static) grad shapes."""
    if uploads["upload_nbytes"] is not None:
        return float(uploads["upload_nbytes"])
    return float(comm_accounting.sample_round_bytes(
        comm_codecs.tree_flat_dim(grad_est), data.num_clients, None,
        participation=participation, with_value=with_value)["up"])


def _wrap_codec_state(state, codec, ef0):
    """The single CommCarry construction site for every driver: attach the
    zeroed EF residuals (built by the ef0 thunk, so the dense path allocates
    nothing) when a codec is in play."""
    if codec is None:
        return state
    return CommCarry(opt=state, ef=ef0())


def _sample_ef0(params0, num_clients: int, cohort: bool = False):
    """Zeroed per-client EF residuals for sample-based q-uploads: a dense
    (I, P) matrix for the reference engine, a keyed `EFStore` (same backing,
    gathered O(S) rows per round) for the cohort engine."""
    dim = comm_codecs.tree_flat_dim(params0)
    if cohort:
        return ef_store_init(num_clients, dim)
    return ef_init_stacked(num_clients, dim)


def _check_cohort(name: str, cohort: bool, participation):
    """The cohort engine IS a partial-participation engine — S is its
    per-round shape; reject cohort=True without participation=S early."""
    if cohort and participation is None:
        raise ValueError(
            f"{name}: cohort=True needs participation=S (the O(S) engine's "
            "per-round cohort size); pass participation= or drop cohort=")


def _cohort_ef_norm(up, topology):
    """ef_norm for the cohort engine: the norm of the cohort's own updated
    residual rows (O(S·P)) — NOT the full (I, P) backing, which would put an
    O(I) reduction back into every round. Stream semantics therefore differ
    from the dense engine's all-clients norm; don't compare across engines."""
    mesh = getattr(topology, "mesh", None)
    return _ef_norm(jax.tree.map(
        lambda store: store.gather(up["cohort"], mesh), up["ef"],
        is_leaf=lambda v: hasattr(v, "gather")))


def _client_stats(stats):
    """Round totals of the per-client stats the client loss returned:
    ``*_max`` is the largest over clients, the rest are summed (empty for
    losses that return none)."""
    return {k: (jnp.max if k.endswith("_max") else jnp.sum)(v)
            for k, v in (stats or {}).items()}


def _stat_res(new_params, old_params, gamma_t):
    """Per-round stationarity residual ‖ω^{t+1} − ω^t‖₂ / γ^t = ‖ω̄^t − ω^t‖₂
    (the update is ω ← (1−γ)ω + γω̄, eq. 5) — the quantity Theorems 1/2
    drive to 0, now a streamed metric on every SSCA driver."""
    d = jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        new_params, old_params)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                        for x in jax.tree.leaves(d))) / jnp.maximum(
                            gamma_t, 1e-30)


def _ef_norm(ef):
    """‖EF residuals‖₂ across every stream — the amount of signal the codec
    is still holding back (decays iff error feedback is keeping up)."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(ef)))


def _dp_sample_rate(participation, num_clients: int) -> float:
    """Accountant subsampling rate q for a sample-based driver: S/I under
    partial participation (dense mask or cohort engine — both draw S of I
    uniformly without replacement, accounted with the standard Poisson-
    subsampling RDP bound, conservative here), 1.0 at full participation."""
    if participation is None or participation >= num_clients:
        return 1.0
    return participation / num_clients


def _dp_metrics(eps_fn, stats, mask, inp):
    """Per-round DP metrics from the uploads["dp"] stats of a sample-based
    round. `mask` is the dense participation mask (None on the cohort path
    and at full participation: every row of `stats` then belongs to a real
    participant). dp_epsilon is ε spent through round t — the accountant's
    cross-round composition evaluated in-graph at inp.t."""
    clipped, noise_sq = stats["clipped"], stats["noise_sq"]
    if mask is None:
        mask = jnp.ones_like(clipped)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return {"dp_epsilon": eps_fn(inp.t),
            "dp_clip_frac": jnp.sum(clipped * mask) / denom,
            "dp_noise_norm": jnp.sqrt(jnp.sum(noise_sq * mask))}


def _dp_feature_metrics(eps_fn, stats, num_clients: int, inp):
    """Feature-round variant: one head stream + I block streams, all
    released every round (the clip fraction averages over the I+1 uploads)."""
    return {"dp_epsilon": eps_fn(inp.t),
            "dp_clip_frac": (stats["head_clipped"]
                             + jnp.sum(stats["blocks_clipped"]))
            / (num_clients + 1.0),
            "dp_noise_norm": jnp.sqrt(stats["head_noise_sq"]
                                      + jnp.sum(stats["blocks_noise_sq"]))}


class _NullSched:
    a1 = a2 = 1.0
    alpha_rho = alpha_gamma = 1.0


_NULL_SCHED = _NullSched()


# ---------------------------------------------------------------------------
# Algorithm 1: unconstrained sample-based FL via mini-batch SSCA
# ---------------------------------------------------------------------------


def make_algorithm1_step(per_sample_loss, data: SampleFedData, fl,
                         participation: Optional[int] = None, codec=None,
                         topology=None, cohort: bool = False, dp=None):
    """One full Algorithm-1 round as a pure (state, RoundInputs) step —
    batch selection, uploads (optionally codec-compressed with error
    feedback), aggregation, surrogate recursion, update — suitable for
    lax.scan (rounds.scan_rounds) or per-round dispatch. With a codec the
    state is a CommCarry(opt=SSCAState, ef=(I, P) residuals). topology
    selects the client-execution engine (DESIGN.md §11). cohort=True runs
    the participant-only O(S) engine (fed.cohort_round, DESIGN.md §14):
    ef becomes a keyed EFStore and topology shards the cohort axis. dp=
    privatizes every q-upload (DESIGN.md §15) and adds the dp_* metrics."""
    _check_cohort("make_algorithm1_step", cohort, participation)
    eps_fn = (privacy_lib.make_eps_fn(
        dp, _dp_sample_rate(participation, data.num_clients))
        if dp is not None else None)

    def body(state, inp, ef):
        if cohort:
            grad_est, val_est, up = fed.cohort_round(
                per_sample_loss, state.params, data, inp.key, fl.batch_size,
                participation, codec=codec, ef=ef, topology=topology, dp=dp)
        else:
            grad_est, val_est, up = fed.sample_round(
                per_sample_loss, state.params, data, inp.key, fl.batch_size,
                participation=participation, codec=codec, ef=ef,
                topology=topology, dp=dp)
        new = optimizer.ssca_step(state, grad_est, fl,
                                  rho_t=inp.rho, gamma_t=inp.gamma)
        with obs_trace.phase("round-metrics"):
            metrics = {"loss_est": val_est,
                       "stat_res": _stat_res(new.params, state.params,
                                             inp.gamma),
                       "upload_bytes": _sample_upload_bytes(
                           up, grad_est, data, participation),
                       "axis_bytes": _axis_bytes_metric(topology, grad_est),
                       **_client_stats(up["client_stats"])}
            if codec is not None:
                metrics["ef_norm"] = (_cohort_ef_norm(up, topology) if cohort
                                      else _ef_norm(up["ef"]))
            if dp is not None:
                metrics.update(_dp_metrics(eps_fn, up["dp"],
                                           up.get("participants"), inp))
        return new, up["ef"], metrics

    return with_comm_carry(codec, body)


def algorithm1(per_sample_loss, params0, data: SampleFedData, fl, rounds: int,
               key, eval_fn=None, eval_every: int = 10,
               participation: Optional[int] = None,
               driver: str = "scan", codec=None, topology=None,
               obs=None, cohort: bool = False, dp=None) -> RunResult:
    step = make_algorithm1_step(per_sample_loss, data, fl, participation,
                                codec, topology, cohort, dp)
    state = _wrap_codec_state(
        optimizer.ssca_init(params0), codec,
        lambda: _sample_ef0(params0, data.num_clients, cohort))
    return _run(step, state, key, rounds, eval_fn, eval_every,
                fl=fl, driver=driver, topology=topology, obs=obs)


# ---------------------------------------------------------------------------
# Algorithm 2: constrained sample-based FL (formulation (40): min ‖ω‖², F <= U)
# ---------------------------------------------------------------------------


def make_algorithm2_step(per_sample_loss, data: SampleFedData, fl,
                         participation: Optional[int] = None, codec=None,
                         topology=None, cohort: bool = False, dp=None):
    _check_cohort("make_algorithm2_step", cohort, participation)
    # NOTE: dp= privatizes the q-grad uploads; the scalar q-value (loss) sums
    # that with_value=True also releases are NOT noised — the accountant
    # covers the gradient stream only (documented limitation, DESIGN.md §15).
    eps_fn = (privacy_lib.make_eps_fn(
        dp, _dp_sample_rate(participation, data.num_clients))
        if dp is not None else None)

    def body(state, inp, ef):
        if cohort:
            grad_est, val_est, up = fed.cohort_round(
                per_sample_loss, state.params, data, inp.key, fl.batch_size,
                participation, with_value=True, codec=codec, ef=ef,
                topology=topology, dp=dp)
        else:
            grad_est, val_est, up = fed.sample_round(
                per_sample_loss, state.params, data, inp.key, fl.batch_size,
                with_value=True, participation=participation, codec=codec,
                ef=ef, topology=topology, dp=dp)
        new = optimizer.ssca_constrained_step(state, grad_est, val_est, fl,
                                              rho_t=inp.rho, gamma_t=inp.gamma)
        with obs_trace.phase("round-metrics"):
            metrics = {"loss_est": val_est, "nu": new.nu, "slack": new.slack,
                       "stat_res": _stat_res(new.params, state.params,
                                             inp.gamma),
                       "cons_viol": jnp.maximum(val_est - fl.cost_limit, 0.0),
                       "upload_bytes": _sample_upload_bytes(
                           up, grad_est, data, participation,
                           with_value=True),
                       "axis_bytes": _axis_bytes_metric(topology, grad_est,
                                                        with_value=True)}
            if codec is not None:
                metrics["ef_norm"] = (_cohort_ef_norm(up, topology) if cohort
                                      else _ef_norm(up["ef"]))
            if dp is not None:
                metrics.update(_dp_metrics(eps_fn, up["dp"],
                                           up.get("participants"), inp))
        return new, up["ef"], metrics

    return with_comm_carry(codec, body)


def algorithm2(per_sample_loss, params0, data: SampleFedData, fl, rounds: int,
               key, eval_fn=None, eval_every: int = 10,
               participation: Optional[int] = None,
               driver: str = "scan", codec=None, topology=None,
               obs=None, cohort: bool = False, dp=None) -> RunResult:
    step = make_algorithm2_step(per_sample_loss, data, fl, participation,
                                codec, topology, cohort, dp)
    state = _wrap_codec_state(
        optimizer.ssca_constrained_init(params0), codec,
        lambda: _sample_ef0(params0, data.num_clients, cohort))
    return _run(step, state, key, rounds, eval_fn, eval_every,
                fl=fl, driver=driver, topology=topology, obs=obs)


def algorithm2_general(obj_loss, cons_loss, params0, data: SampleFedData, fl,
                       rounds: int, key, eval_fn=None, eval_every: int = 10,
                       participation: Optional[int] = None,
                       driver: str = "scan", codec=None,
                       topology=None, obs=None,
                       cohort: bool = False, dp=None) -> RunResult:
    """Full Algorithm 2: sampled nonconvex objective AND constraint. With a
    codec the objective and constraint q-uploads carry separate EF
    residuals (ef = {"obj": (I, P), "cons": (I, P)}); under a sharded
    topology both aggregations psum over the client axes (two streams).
    cohort=True runs both streams through the O(S) engine — the shared
    participation key makes each stream re-derive the SAME cohort ids, and
    each stream's residuals live in their own keyed EFStore. dp= privatizes
    BOTH q-grad streams (independent noise keys per stream), so the
    accountant composes 2 releases per round."""
    _check_cohort("algorithm2_general", cohort, participation)
    eps_fn = (privacy_lib.make_eps_fn(
        dp, _dp_sample_rate(participation, data.num_clients),
        releases_per_round=2) if dp is not None else None)

    def body(state, inp, ef):
        ef = ef if ef is not None else {"obj": None, "cons": None}
        k1, k2 = jax.random.split(inp.key)
        # ONE participant set per round: both the objective and the constraint
        # statistics are uploaded by the same S clients (faithful protocol).
        pk = jax.random.fold_in(inp.key, 0x5ca)
        if cohort:
            og, _, uo = fed.cohort_round(obj_loss, state.params, data, k1,
                                         fl.batch_size, participation,
                                         participation_key=pk, codec=codec,
                                         ef=ef["obj"], topology=topology,
                                         dp=dp)
            cg, cv, uc = fed.cohort_round(cons_loss, state.params, data, k2,
                                          fl.batch_size, participation,
                                          with_value=True,
                                          participation_key=pk, codec=codec,
                                          ef=ef["cons"], topology=topology,
                                          dp=dp)
        else:
            og, _, uo = fed.sample_round(obj_loss, state.params, data, k1,
                                         fl.batch_size,
                                         participation=participation,
                                         participation_key=pk, codec=codec,
                                         ef=ef["obj"], topology=topology,
                                         dp=dp)
            cg, cv, uc = fed.sample_round(cons_loss, state.params, data, k2,
                                          fl.batch_size, with_value=True,
                                          participation=participation,
                                          participation_key=pk, codec=codec,
                                          ef=ef["cons"], topology=topology,
                                          dp=dp)
        new = optimizer.ssca_general_constrained_step(
            state, og, cg, cv, fl, rho_t=inp.rho, gamma_t=inp.gamma)
        new_ef = {"obj": uo["ef"], "cons": uc["ef"]}
        with obs_trace.phase("round-metrics"):
            bts = (_sample_upload_bytes(uo, og, data, participation)
                   + _sample_upload_bytes(uc, cg, data, participation,
                                          with_value=True))
            metrics = {"cons_est": cv, "nu": new.nu, "slack": new.slack,
                       "stat_res": _stat_res(new.params, state.params,
                                             inp.gamma),
                       "cons_viol": jnp.maximum(cv - fl.cost_limit, 0.0),
                       "upload_bytes": bts,
                       "axis_bytes": (_axis_bytes_metric(topology, og)
                                      + _axis_bytes_metric(topology, cg,
                                                           with_value=True))}
            if codec is not None:
                metrics["ef_norm"] = (
                    _cohort_ef_norm({"cohort": uo["cohort"], "ef": new_ef},
                                    topology)
                    if cohort else _ef_norm(new_ef))
            if dp is not None:
                pm = uo.get("participants")
                mo = _dp_metrics(eps_fn, uo["dp"], pm, inp)
                mc = _dp_metrics(eps_fn, uc["dp"], pm, inp)
                metrics.update({
                    "dp_epsilon": mo["dp_epsilon"],
                    "dp_clip_frac": 0.5 * (mo["dp_clip_frac"]
                                           + mc["dp_clip_frac"]),
                    "dp_noise_norm": jnp.sqrt(
                        jnp.square(mo["dp_noise_norm"])
                        + jnp.square(mc["dp_noise_norm"]))})
        return new, new_ef, metrics

    step = with_comm_carry(codec, body)
    state = _wrap_codec_state(
        optimizer.ssca_general_constrained_init(params0), codec,
        lambda: {"obj": _sample_ef0(params0, data.num_clients, cohort),
                 "cons": _sample_ef0(params0, data.num_clients, cohort)})
    return _run(step, state, key, rounds, eval_fn, eval_every,
                fl=fl, driver=driver, topology=topology, obs=obs)


# ---------------------------------------------------------------------------
# Algorithm 3: unconstrained feature-based FL via mini-batch SSCA
# ---------------------------------------------------------------------------


def _run_feature(step_fn, state, key, num_rounds: int,
                 eval_fn: Optional[Callable], eval_every: int,
                 extract_params=None, fl=None, driver: str = "scan",
                 topology=None, obs=None):
    """Feature-based `_run`: same shim, but the per-client carry placement is
    the feature-EF dict layout (rounds.run_feature_rounds /
    topology.place_feature_state). Shared with baselines' feature drivers."""
    fl = fl if fl is not None else _NULL_SCHED
    return rounds_lib.run_feature_rounds(
        step_fn, state, fl, key, num_rounds, eval_fn=eval_fn,
        eval_every=eval_every, extract_params=extract_params, driver=driver,
        topology=topology, obs=obs)


def _feature_axis_bytes(topology, uploads):
    """Static per-round bytes over the client mesh axis for a feature round
    (0.0 for local): the all_gather realization of the step-4 h-broadcast
    moves the full (I, B, J) h; uploads only supplies the (trace-time
    static) element count."""
    shards = getattr(topology, "num_shards", 1) if topology is not None else 1
    return float(comm_accounting.all_gather_axis_bytes(
        uploads["h_exchange"].size, shards))


def _feature_upload_bytes(uploads, grad_est, data, batch_size: int):
    """Per-round uplink bytes of a feature-based round: the codec path reuses
    fed.feature_round's exact figure, the dense path derives fp32 bytes from
    the (static) upload shapes. Shared with baselines.feature_sgd."""
    if uploads["upload_nbytes"] is not None:
        return float(uploads["upload_nbytes"])
    return float(comm_accounting.feature_round_bytes(
        comm_codecs.tree_flat_dim(grad_est["w0"]),
        [comm_codecs.tree_flat_dim(grad_est["blocks"], stacked=True)]
        * data.num_clients,
        batch_size, uploads["h_exchange"].shape[-1],
        data.num_clients)["up"])


def _feature_ef0(params0, num_clients: int):
    """Zeroed EF residuals for the feature-based uploads: one head stream +
    one per-client block stream."""
    return {"w0": ef_init(comm_codecs.tree_flat_dim(params0["w0"])),
            "blocks": ef_init_stacked(
                num_clients,
                comm_codecs.tree_flat_dim(params0["blocks"], stacked=True))}


def _make_feature_step(head_loss_from_h, client_h, data, fl, codec,
                       update_fn, topology=None, dp=None):
    """Shared Algorithm-3/4 step body: feature_round + the given optimizer
    update, with optional codec/EF threading. topology selects the feature
    client-execution engine (DESIGN.md §12). dp= privatizes the head and
    block q-uploads — all I clients release every round (q = 1) and the
    head + block streams count as 2 releases per round for the accountant;
    the step-4 h-exchange stays unprivatized (fed.feature_round docstring)."""
    eps_fn = (privacy_lib.make_eps_fn(dp, 1.0, releases_per_round=2)
              if dp is not None else None)

    def body(state, inp, ef):
        grad_est, val_est, up = fed.feature_round(
            state.params, data, inp.key, fl.batch_size, head_loss_from_h,
            client_h, codec=codec, ef=ef, topology=topology, dp=dp)
        new, metrics = update_fn(state, grad_est, val_est, inp)
        with obs_trace.phase("round-metrics"):
            metrics["stat_res"] = _stat_res(new.params, state.params,
                                            inp.gamma)
            metrics["upload_bytes"] = _feature_upload_bytes(
                up, grad_est, data, fl.batch_size)
            metrics["axis_bytes"] = _feature_axis_bytes(topology, up)
            if codec is not None:
                metrics["ef_norm"] = _ef_norm(up["ef"])
            if dp is not None:
                metrics.update(_dp_feature_metrics(eps_fn, up["dp"],
                                                   data.num_clients, inp))
        return new, up["ef"], metrics

    return with_comm_carry(codec, body)


def algorithm3(head_loss_from_h, client_h, params0, data: FeatureFedData, fl,
               rounds: int, key, eval_fn=None, eval_every: int = 10,
               driver: str = "scan", codec=None, topology=None,
               obs=None, dp=None) -> RunResult:
    def update(state, grad_est, val_est, inp):
        new = optimizer.ssca_step(state, grad_est, fl,
                                  rho_t=inp.rho, gamma_t=inp.gamma)
        return new, {"loss_est": val_est}

    step = _make_feature_step(head_loss_from_h, client_h, data, fl, codec,
                              update, topology, dp)
    state = _wrap_codec_state(optimizer.ssca_init(params0), codec,
                              lambda: _feature_ef0(params0, data.num_clients))
    return _run_feature(step, state, key, rounds, eval_fn, eval_every,
                        fl=fl, driver=driver, topology=topology, obs=obs)


# ---------------------------------------------------------------------------
# Algorithm 4: constrained feature-based FL
# ---------------------------------------------------------------------------


def algorithm4(head_loss_from_h, client_h, params0, data: FeatureFedData, fl,
               rounds: int, key, eval_fn=None, eval_every: int = 10,
               driver: str = "scan", codec=None, topology=None,
               obs=None, dp=None) -> RunResult:
    def update(state, grad_est, val_est, inp):
        new = optimizer.ssca_constrained_step(state, grad_est, val_est, fl,
                                              rho_t=inp.rho, gamma_t=inp.gamma)
        return new, {"loss_est": val_est, "nu": new.nu, "slack": new.slack,
                     "cons_viol": jnp.maximum(val_est - fl.cost_limit, 0.0)}

    step = _make_feature_step(head_loss_from_h, client_h, data, fl, codec,
                              update, topology, dp)
    state = _wrap_codec_state(optimizer.ssca_constrained_init(params0), codec,
                              lambda: _feature_ef0(params0, data.num_clients))
    return _run_feature(step, state, key, rounds, eval_fn, eval_every,
                        fl=fl, driver=driver, topology=topology, obs=obs)
