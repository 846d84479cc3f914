"""Scan-compiled multi-round federated driver (see DESIGN.md §6).

The seed runtime drove communication rounds from a Python loop: one XLA
dispatch per round, schedule powers recomputed from the carried t, metrics
only observable at chunk boundaries. This module folds the *entire* SSCA
round chain — client mini-batch selection (paper step 4), q-statistic uploads,
N_i/(B_i·N) aggregation, surrogate recursion (eq. 9), and the closed-form
update (eq. 10) / constrained Lemma-1 step — into a single ``lax.scan`` over
rounds, so a K-round epoch is ONE dispatch:

    inputs = make_inputs(fl, t0, K, key)         # per-round (key, ρ^t, γ^t)
    state, hist = scan_rounds(step_fn, state, inputs)

Per-round ρ^t/γ^t are computed ahead of the scan (including the paper's
ρ^(1)=1 convention) and threaded through it as stacked inputs alongside the
per-round PRNG keys; the round counter t rides in the optimizer state as the
scan carry. Every step emits a metrics dict of scalars, which the scan stacks
into (K,)-arrays — full per-round trajectories for free, where the Python
loop only saw chunk boundaries. `run_rounds` builds each chunk's inputs in
one compiled call (`chunk_inputs`) and keeps the history series that start
on the host there, so between two dispatches the device waits on no eager
op of the driver.

``loop_rounds`` is the semantics-identical per-round-dispatch reference used
by the equivalence test (tests/test_rounds.py) and the scan-vs-loop
rounds-per-second benchmark (benchmarks/rounds_bench.py).

The scan composes with the topology layer (core/topology.py, DESIGN.md §11):
a step whose round body runs clients under a ``ShardedTopology`` embeds a
shard_map inside the scanned step, so K rounds across D devices are still
ONE dispatch, with the per-round q-aggregation as a weighted psum. The only
per-client state in the carry is the error-feedback residual matrix (I, P);
``run_rounds(..., topology=)`` pre-places it over the client axes
(`topology.place_state`) so the carry starts sharded instead of being
resharded by the first shard_map entry.
"""
from __future__ import annotations

import functools
import weakref
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import schedules
from repro.obs import trace as obs_trace


class RoundInputs(NamedTuple):
    """Per-round scan inputs: each leaf has a leading (K,) round axis."""
    key: jnp.ndarray          # (K, 2) per-round PRNG keys
    rho: jnp.ndarray          # (K,) ρ^t
    gamma: jnp.ndarray        # (K,) γ^t
    t: jnp.ndarray            # (K,) global 1-based round numbers (int32) —
                              # labels the obs tap's streamed rows and drives
                              # the DP accountant's in-graph ε-so-far
                              # (privacy.make_eps_fn: RDP composition is
                              # linear in t); steps may ignore it

    @property
    def num_rounds(self):
        return self.rho.shape[0]


def schedule_arrays(fl, t_start: int, num_rounds: int):
    """(ρ^t, γ^t) for t = t_start .. t_start+K-1, with the paper's ρ^(1) = 1
    convention applied (§III-A, before eq. (11)) — matches optimizer._sched."""
    t = t_start + jnp.arange(num_rounds, dtype=jnp.int32)
    rho = jnp.where(t == 1, 1.0, schedules.rho(t, fl.a1, fl.alpha_rho))
    gamma = schedules.gamma(t, fl.a2, fl.alpha_gamma)
    return rho, gamma


def make_inputs(fl, t_start: int, num_rounds: int, key) -> RoundInputs:
    """The K = num_rounds rounds' inputs from t_start on. ``t_start`` and
    the schedule constants of ``fl`` may be traced (`chunk_inputs`)."""
    rho, gamma = schedule_arrays(fl, t_start, num_rounds)
    return RoundInputs(key=jax.random.split(key, num_rounds),
                       rho=rho, gamma=gamma,
                       t=t_start + jnp.arange(num_rounds, dtype=jnp.int32))


class Schedule(NamedTuple):
    """The schedule constants of an FLConfig, passed to `chunk_inputs`'
    program as traced values: as compile-time constants the compiler
    specialises t**alpha for some exponents (t**1.0 -> t, t**2.0 -> t*t),
    and the compiled schedule would no longer equal `make_inputs`' eager
    one bit for bit."""
    a1: float
    alpha_rho: float
    a2: float
    alpha_gamma: float


@functools.lru_cache(maxsize=None)
def _chunk_inputs_jit(num_rounds: int):
    """One program per chunk size K; t_start and the schedule are traced,
    so successive chunks of one size never recompile."""
    def chunk(key, t_start, sched):
        key, sub = jax.random.split(key)
        return key, make_inputs(sched, t_start, num_rounds, sub)
    return jax.jit(chunk)


def chunk_inputs(fl, key, t_start: int, num_rounds: int):
    """``(next_key, inputs)``: `run_rounds`' per-chunk split of its key and
    `make_inputs` on the split-off half, as ONE compiled call — bit for bit
    what ``key, sub = jax.random.split(key)`` and
    ``make_inputs(fl, t_start, num_rounds, sub)`` give eagerly."""
    sched = Schedule(fl.a1, fl.alpha_rho, fl.a2, fl.alpha_gamma)
    return _chunk_inputs_jit(num_rounds)(key, t_start, sched)


def scan_rounds(step_fn: Callable, state, inputs: RoundInputs):
    """Run K = inputs.num_rounds rounds as ONE jitted lax.scan dispatch.

    step_fn(state, inp) -> (state, metrics-dict-of-scalars); returns the final
    state and the metrics dict stacked to (K,) arrays. The jitted callable is
    cached per step_fn identity (bounded LRU), so chunked callers and repeat
    invocations with the same step compile once. A state too large to hold
    twice is donated (`scan_jit_for`): the caller's ``state`` is then
    consumed.
    """
    return scan_jit_for(step_fn, state)(state, inputs)


# Caches keyed weakly by step_fn identity. Cross-CALL reuse (not just within
# one run_rounds) is load-bearing: chunked runs and the benchmark's timing
# repeats re-invoke scan_rounds/loop_rounds with the same step and must not
# retrace. Weak keying ties each entry's lifetime to the caller's step
# closure — a step captures its whole client dataset, and the compiled
# executable bakes those arrays in as constants, so the entry (and the
# dataset) is released as soon as the caller drops the closure. The cached
# callable itself only holds a weakref to step_fn, which is live whenever
# the entry is reachable.
_SCAN_CACHE = weakref.WeakKeyDictionary()
_SCAN_DONATED_CACHE = weakref.WeakKeyDictionary()
_STEP_CACHE = weakref.WeakKeyDictionary()

# a carried state above this share of the device's memory is donated to the
# K-round program: held by the caller too, it would be in memory twice
DONATE_SHARE = 1 / 8


def _weak_cached(cache, step_fn, make):
    fn = cache.get(step_fn)
    if fn is None:
        fn = make(weakref.ref(step_fn))
        cache[step_fn] = fn
    return fn


def _scan_jit(step_fn):
    # the step runs under the "round" named scope so profiler dumps
    # attribute device time to the protocol phase (obs/trace.py)
    return _weak_cached(
        _SCAN_CACHE, step_fn,
        lambda ref: jax.jit(
            lambda state, inputs: jax.lax.scan(
                obs_trace.scoped("round", ref()), state, inputs)))


def _scan_jit_donated(step_fn):
    """`_scan_jit` whose program takes over (donates) the carried state."""
    return _weak_cached(
        _SCAN_DONATED_CACHE, step_fn,
        lambda ref: jax.jit(
            lambda state, inputs: jax.lax.scan(
                obs_trace.scoped("round", ref()), state, inputs),
            donate_argnums=0))


def donates(state) -> bool:
    """Whether the K-round program donates ``state``: its bytes exceed
    DONATE_SHARE of the device's memory (a language model's parameters and
    surrogate buffer). Smaller states stay the caller's to reuse."""
    from repro.core.topology import device_bytes
    nbytes = sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(state))
    return nbytes > DONATE_SHARE * device_bytes()


def scan_jit_for(step_fn, state):
    """The jitted K-round program of ``step_fn`` for ``state``."""
    return (_scan_jit_donated if donates(state) else _scan_jit)(step_fn)


def _step_jit(step_fn):
    return _weak_cached(
        _STEP_CACHE, step_fn,
        lambda ref: jax.jit(
            lambda state, inp: obs_trace.scoped("round", ref())(state, inp)))


def loop_rounds(step_fn: Callable, state, inputs: RoundInputs):
    """Reference driver: same step, one jitted dispatch per round (the seed's
    execution model). Kept for the equivalence test and the benchmark. The
    jitted step shares the bounded per-step cache, so repeat calls (benchmark
    timing loops, chunked runs) do not retrace."""
    step = _step_jit(step_fn)
    ms = []
    for r in range(inputs.num_rounds):
        state, m = step(state, jax.tree.map(lambda x: x[r], inputs))
        ms.append(m)
    stacked = {k: jnp.stack([m[k] for m in ms]) for k in ms[0]} if ms else {}
    return state, stacked


class RunResult(NamedTuple):
    params: object
    history: dict             # eval-metric name -> (n_evals,) + per-round arrays
    final_state: object       # full scan carry (incl. any CommCarry EF state)


def unwrap_comm(state):
    """Peel communication-compression carries off a scan state.

    With a codec, drivers wrap their optimizer state in
    ``repro.comm.error_feedback.CommCarry(opt=..., ef=...)`` so the
    error-feedback residuals round-trip through the ``lax.scan`` carry as
    regular pytree state. This walks ``.opt`` links until it reaches the
    state that owns ``.params`` (no-op for unwrapped states)."""
    while not hasattr(state, "params") and hasattr(state, "opt"):
        state = state.opt
    return state


def _default_extract(state):
    return unwrap_comm(state).params


ENGINES = {"scan": scan_rounds, "loop": loop_rounds}


def chunk_sizes(rounds: int, chunk: int):
    """Split `rounds` into chunk-sized dispatches, never dropping the partial
    final chunk."""
    chunk = max(1, min(chunk, rounds))
    sizes = [chunk] * (rounds // chunk)
    if rounds % chunk:
        sizes.append(rounds % chunk)
    return sizes


def _check_eval_keys(metrics, step_metric_names):
    """Eval-hook metrics share the history dict with the per-round scan-step
    series — a same-named key would silently overwrite the (K,) series (or
    corrupt the "round" index). Collisions are an error, not a merge."""
    reserved = {"round", "round_t"}
    reserved.update("round_" + k for k in step_metric_names)
    bad = sorted(set(metrics) & reserved)
    if bad:
        raise ValueError(
            f"eval_fn metric keys {bad} collide with the per-round history "
            "series (\"round\", \"round_t\", and \"round_<step metric>\" "
            "are reserved) — rename them, e.g. namespace as 'eval/<name>'")


def _emit_eval(obs, metrics, t_global: int):
    """Stream an eval-hook result through the obs tap (scalar-coercible
    values only — eval hooks may return arrays, which stay history-only)."""
    row = {"kind": "eval", "t": int(t_global)}
    for k, v in metrics.items():
        try:
            row[k] = float(v)
        except (TypeError, ValueError):
            continue
    # no sync needed: events ride the drainer queue behind the chunk's
    # flush, so the finished chunk's round rows land first anyway
    obs.emit_event(row)


def _host_array(values):
    """``values`` as a host array, in the dtype ``jnp.asarray`` would give."""
    values = np.asarray(values)
    return values.astype(jax.dtypes.canonicalize_dtype(values.dtype),
                         copy=False)


def _series(values):
    """One eval series: values the eval hook returned as device arrays are
    stacked on the device once; host values (the round counts, Python
    floats) stay on the host."""
    if any(isinstance(v, jax.Array) for v in values):
        return jnp.asarray(values)
    return _host_array(values)


def run_rounds(step_fn: Callable, state, fl, key, rounds: int,
               eval_fn: Optional[Callable] = None, eval_every: int = 0,
               extract_params: Optional[Callable] = None,
               t_start: int = 1, driver: str = "scan",
               topology=None, obs=None) -> RunResult:
    """High-level driver: scan-compile rounds, with optional periodic host
    evaluation between scan chunks.

    With eval_fn=None the K rounds are one dispatch; with eval_every=E each
    E-round chunk is one dispatch and eval_fn(params, state) runs between
    chunks. history carries the eval series under their own names keyed by
    "round", plus every step metric as a full (K,) per-round series under
    "round_<name>" (with "round_t" = t_start..t_start+K-1). Eval metric
    names that would shadow a per-round series raise (no silent overwrite).

    ``topology`` (core/topology.py) is the client-execution engine the step
    was built with; passing it here lets the driver pre-place per-client
    carry state (EF residuals) over the mesh before the first dispatch.

    ``obs`` (repro.obs.MetricStream) streams every round's metrics to host
    sinks *while* each dispatch runs, and interleaves eval results into the
    same log; trajectories and the returned history are bitwise-unchanged
    (DESIGN.md §13).
    """
    engine = ENGINES[driver]
    if topology is not None:
        state = topology.place_state(state)
    extract_params = extract_params or _default_extract
    if rounds <= 0:
        return RunResult(extract_params(state), {"round": jnp.zeros((0,))},
                         state)
    # eval_every <= 0 with an eval_fn means "evaluate every round" (seed
    # semantics); without an eval_fn all rounds are one dispatch.
    chunk = (max(1, eval_every) if eval_fn is not None else rounds)
    sizes = chunk_sizes(rounds, chunk)

    hist: dict = {"round": []}
    per_round: list = []
    t0 = t_start
    # host spans (obs/trace.DRIVER_SPANS) put each device idle gap down to
    # the driver's work in it: the inputs call, the K-round enqueue, the
    # eval hook's host read, the history assembly
    for size in sizes:
        with obs_trace.host_span("rounds/inputs"):
            key, inputs = chunk_inputs(fl, key, t0, size)
        with obs_trace.host_span("rounds/launch", rounds=size, t=t0):
            if obs is not None:
                state, ms = obs.run(step_fn, state, inputs, driver=driver)
            else:
                state, ms = engine(step_fn, state, inputs)
        t0 += size
        per_round.append(ms)
        if eval_fn is not None:
            with obs_trace.host_span("rounds/eval"):
                metrics = eval_fn(extract_params(state), state)
                _check_eval_keys(metrics, per_round[0])
                for k, v in metrics.items():
                    hist.setdefault(k, []).append(v)
                hist["round"].append(t0 - t_start)
                if obs is not None:
                    _emit_eval(obs, metrics, t0 - 1)
    with obs_trace.host_span("rounds/history"):
        history = {k: _series(v) for k, v in hist.items()}
        if per_round and per_round[0]:
            for k in per_round[0]:
                ms = [m[k] for m in per_round]
                history["round_" + k] = (ms[0] if len(ms) == 1
                                         else jnp.concatenate(ms))
            history["round_t"] = _host_array(np.arange(t_start, t0))
    return RunResult(extract_params(state), history, state)


def run_feature_rounds(step_fn: Callable, state, fl, key, rounds: int,
                       eval_fn: Optional[Callable] = None,
                       eval_every: int = 0,
                       extract_params: Optional[Callable] = None,
                       t_start: int = 1, driver: str = "scan",
                       topology=None, obs=None) -> RunResult:
    """Feature-based (vertical FL, Algorithms 3/4) counterpart of
    :func:`run_rounds`: K vertical rounds — h-exchange, head + block
    q-uploads, 1/B aggregation (eq. 16), SSCA update — compile to ONE
    dispatch, with the codec/EF state riding the scan carry.

    The only difference from `run_rounds` is carry placement: a feature
    CommCarry's EF state is a *dict* of streams, and
    ``topology.place_feature_state`` shards the per-client block residuals
    (I, Pb) over the client axes while the single head stream stays
    replicated — matching `feature_sum`'s out_specs so the carry never
    reshards across the K scanned rounds.
    """
    if topology is not None:
        place = getattr(topology, "place_feature_state", None)
        if place is not None:
            state = place(state)
    return run_rounds(step_fn, state, fl, key, rounds, eval_fn=eval_fn,
                      eval_every=eval_every, extract_params=extract_params,
                      t_start=t_start, driver=driver, obs=obs)
