"""Jaxpr contract checkers for the compiled round step.

Traces the *actual* step built by ``make_algorithm1_step`` under
``lax.scan`` — exactly what ``rounds.scan_rounds`` compiles — for the
full config matrix (dense/cohort × local/sharded × identity/int8+EF ×
dp on/off) and asserts structural properties on the closed jaxpr that
no pointwise test can see:

* **scan purity** — no ``io_callback`` / ``pure_callback`` /
  ``debug_callback`` equations anywhere in the scan body.  The obs
  callback transport keeps its single ``io_callback`` in a separate
  companion program (``MetricStream._flusher``), which is checked to
  contain *exactly one* — the registered tap — while the scan stays pure
  even with a stream attached.
* **DP-before-encode** — the DP noise draw (``erf_inv``, the only
  normal-sampling primitive in the round body) appears strictly before
  the first int8 ``convert_element_type`` of the codec encode chain, so
  EF residuals and the wire only ever see privatized uploads
  (DESIGN.md §15).  Without ``dp=`` the body must contain no normal
  draw at all.
* **collective axes** — every ``psum``/``all_gather``/… axis name is ⊆
  the active topology's mesh axes (the ``*_invariant`` variants that
  ``shard_map(check_vma=True)`` binds included); the local topology
  compiles to zero collectives.
* **wire dtypes** — ``codec.encode`` output dtypes equal the codec's
  wire spec (int8 values + f32 scales for the quantizer, f32 for
  identity/dense), via ``jax.eval_shape``.
* **no f64** — no float64/complex128 aval anywhere in the round body.

Checkers operate on the flattened equation list (depth-first over
sub-jaxprs, which preserves topological order), so ordering assertions
hold through ``pjit``/``shard_map``/``while_loop`` nesting.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable

import jax
import jax.numpy as jnp

from repro.comm.codecs import (DenseEncoded, QuantEncoded, TopKEncoded,
                               make_codec, tree_flat_dim)
from repro.core import algorithms, fed, optimizer, rounds
from repro.core.privacy import DPConfig
from repro.core.topology import LocalTopology, ShardedTopology
from repro.launch.mesh import make_client_mesh
from repro.models import mlp

_CALLBACK_PRIMS = frozenset({"io_callback", "pure_callback", "debug_callback"})
_COLLECTIVE_PRIMS = frozenset({"psum", "all_gather", "all_to_all", "ppermute",
                               "pmax", "pmin", "pmean", "reduce_scatter"})

# Tiny but structurally faithful problem: ragged-free I=16 clients so the
# 8-device CI mesh divides both the population and the S=8 cohort.
_I, _N, _P, _L, _J, _B, _S = 16, 6, 10, 3, 8, 4, 8


@dataclasses.dataclass
class ContractViolation:
    config: str
    check: str
    detail: str

    def render(self) -> str:
        return f"[{self.config}] {self.check}: {self.detail}"


@dataclasses.dataclass
class ContractReport:
    configs: list[str]
    violations: list[ContractViolation]

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> dict:
        return {"num_configs": len(self.configs),
                "configs": self.configs,
                "ok": self.ok,
                "violations": [dataclasses.asdict(v) for v in self.violations]}

    def render_text(self) -> str:
        lines = [v.render() for v in self.violations]
        lines.append(f"contracts: {len(self.configs)} config(s), "
                     f"{len(self.violations)} violation(s)")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# jaxpr plumbing
# ---------------------------------------------------------------------------


def _iter_eqns(jaxpr) -> Iterable:
    """Depth-first flatten of all equations, preserving topological order."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                yield from _iter_eqns(sub)


def _sub_jaxprs(val) -> Iterable:
    if hasattr(val, "eqns"):
        yield val
    elif hasattr(val, "jaxpr"):
        yield val.jaxpr
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _sub_jaxprs(v)


def find_scan_body(closed):
    """The body jaxpr of the (single) lax.scan in a traced program."""
    for eqn in _iter_eqns(closed.jaxpr):
        if eqn.primitive.name == "scan":
            return eqn.params["jaxpr"].jaxpr
    raise AssertionError("no scan equation found in traced program")


def trace_scan(step_fn, state, inputs):
    """Trace exactly what rounds.scan_rounds compiles (sans the jit)."""
    closed = jax.make_jaxpr(
        lambda s, i: jax.lax.scan(step_fn, s, i))(state, inputs)
    return closed, find_scan_body(closed)


# ---------------------------------------------------------------------------
# checkers (each returns a list of violation detail strings)
# ---------------------------------------------------------------------------


def check_scan_pure(body) -> list[str]:
    out = []
    for eqn in _iter_eqns(body):
        if eqn.primitive.name in _CALLBACK_PRIMS or "callback" in eqn.primitive.name:
            out.append(f"host-effect primitive '{eqn.primitive.name}' inside "
                       "the scan body; host taps must live in the obs "
                       "companion program, never in the round")
    return out


def check_dp_before_encode(body, dp_on: bool, int8: bool) -> list[str]:
    eqns = list(_iter_eqns(body))
    noise_idx = [i for i, e in enumerate(eqns)
                 if e.primitive.name == "erf_inv"]
    enc_idx = [i for i, e in enumerate(eqns)
               if e.primitive.name == "convert_element_type"
               and getattr(e.params.get("new_dtype"), "name", "") == "int8"]
    out = []
    if dp_on and not noise_idx:
        out.append("dp enabled but no gaussian draw (erf_inv) in the body")
    if not dp_on and noise_idx:
        out.append("gaussian draw (erf_inv) in the body without dp enabled")
    if int8 and not enc_idx:
        out.append("int8 codec active but no int8 convert_element_type "
                   "in the body")
    if dp_on and int8 and noise_idx and enc_idx:
        if min(noise_idx) >= min(enc_idx):
            out.append(
                f"DP noise (eqn {min(noise_idx)}) does not precede the codec "
                f"int8 encode (eqn {min(enc_idx)}): EF residuals/wire would "
                "see raw uploads (DESIGN.md §15 ordering)")
    return out


def check_collective_axes(body, allowed: tuple[str, ...]) -> list[str]:
    out = []
    for eqn in _iter_eqns(body):
        # shard_map with check_vma binds the replication-typed variants
        # (psum_invariant, all_gather_invariant, ...) of each collective
        base = eqn.primitive.name.removesuffix("_invariant")
        if base not in _COLLECTIVE_PRIMS:
            continue
        axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
        if not isinstance(axes, (tuple, list)):
            axes = (axes,)
        names = tuple(a for a in axes if isinstance(a, str))
        bad = [a for a in names if a not in allowed]
        if bad:
            out.append(f"collective '{eqn.primitive.name}' over axes {bad} "
                       f"not declared by the active topology (mesh axes: "
                       f"{allowed or '()'})")
    return out


# wire spec: Encoded-type -> {field: dtype}; None entries are not checked
_WIRE_SPECS = {
    DenseEncoded: {"values": jnp.float32},
    QuantEncoded: {"values": jnp.int8, "scales": jnp.float32},
    TopKEncoded: {"values": jnp.float32, "indices": jnp.int32},
}


def check_wire_dtypes(codec, dim: int) -> list[str]:
    if codec is None:
        return []
    key = jax.random.PRNGKey(0)
    enc = jax.eval_shape(lambda x: codec.encode(x, key),
                         jax.ShapeDtypeStruct((dim,), jnp.float32))
    return _check_encoded(enc, type(codec).__name__)


def _check_encoded(enc, codec_name: str) -> list[str]:
    out = []
    spec = _WIRE_SPECS.get(type(enc))
    if spec is None:
        # chain codecs nest; check every Encoded-typed field
        for fname in getattr(enc, "_fields", ()):
            sub = getattr(enc, fname)
            if type(sub) in _WIRE_SPECS:
                out.extend(_check_encoded(sub, codec_name))
        return out
    for fname, want in spec.items():
        got = getattr(enc, fname).dtype
        if got != want:
            out.append(f"{codec_name} wire field '{fname}' is {got}, codec "
                       f"spec pins {jnp.dtype(want).name}")
    return out


def check_no_f64(body) -> list[str]:
    for eqn in _iter_eqns(body):
        for var in eqn.outvars:
            dtype = getattr(var.aval, "dtype", None)
            # str() handles extended dtypes (PRNG key avals) that
            # jnp.dtype() cannot interpret
            if dtype is not None and str(dtype) in ("float64", "complex128"):
                return [f"float64 aval from '{eqn.primitive.name}' in the "
                        "round body; the stack is pinned to f32"]
    return []


def check_obs_tap() -> list[str]:
    """The callback transport's companion program: exactly one io_callback."""
    from repro.obs.metrics import MetricStream

    stream = MetricStream(transport="callback")
    flush = stream._flusher(("loss_est",))
    t_vec = jnp.arange(2, dtype=jnp.int32)
    ms = {"loss_est": jnp.zeros((2,), jnp.float32)}
    closed = jax.make_jaxpr(lambda t, m: flush.__wrapped__(t, m))(t_vec, ms)
    n = sum(1 for e in _iter_eqns(closed.jaxpr)
            if e.primitive.name in _CALLBACK_PRIMS)
    stream.close()
    if n != 1:
        return [f"obs flusher program has {n} callback eqns, expected "
                "exactly 1 (the registered tap)"]
    return []


# ---------------------------------------------------------------------------
# the config matrix
# ---------------------------------------------------------------------------


def _problem(key=None):
    from repro.configs.base import FLConfig

    key = jax.random.PRNGKey(7) if key is None else key
    kd, kp = jax.random.split(key)
    feats = jax.random.normal(kd, (_I * _N, _P), jnp.float32)
    labels = jax.nn.one_hot(
        jax.random.randint(jax.random.fold_in(kd, 1), (_I * _N,), 0, _L), _L)
    data = fed.partition_samples(feats, labels, _I)
    params0 = mlp.init(kp, _P, _J, _L)
    fl = FLConfig(num_clients=_I, batch_size=_B)
    return data, params0, fl


def _topology(kind: str):
    if kind == "local":
        return LocalTopology(), ()
    topo = ShardedTopology(make_client_mesh(axis="data"))
    return topo, topo.axes


def matrix_configs():
    """(name, engine, topology, codec, dp) for the full contract matrix."""
    configs = []
    for engine in ("dense", "cohort"):
        for topo in ("local", "sharded"):
            for codec in ("identity", "int8"):
                for dp in (False, True):
                    configs.append((f"{engine}/{topo}/{codec}/"
                                    f"{'dp' if dp else 'nodp'}",
                                    engine, topo, codec, dp))
    return configs


def run_config(name: str, engine: str, topo_kind: str, codec_name: str,
               dp_on: bool, execute: bool = True) -> list[ContractViolation]:
    """Trace one matrix config and run every contract checker on it."""
    data, params0, fl = _problem()
    topo, axes = _topology(topo_kind)
    codec = make_codec(codec_name)
    dp = DPConfig(clip_norm=1.0, noise_multiplier=1.0) if dp_on else None
    cohort = engine == "cohort"
    participation = _S if cohort else None

    step = algorithms.make_algorithm1_step(
        mlp.per_sample_loss, data, fl, participation=participation,
        codec=codec, topology=topo, cohort=cohort, dp=dp)
    state = algorithms._wrap_codec_state(
        optimizer.ssca_init(params0), codec,
        lambda: algorithms._sample_ef0(params0, data.num_clients, cohort))
    inputs = rounds.make_inputs(fl, 1, 3, jax.random.PRNGKey(3))

    _, body = trace_scan(step, state, inputs)
    details: list[tuple[str, list[str]]] = [
        ("scan_pure", check_scan_pure(body)),
        ("dp_before_encode",
         check_dp_before_encode(body, dp_on, codec_name == "int8")),
        ("collective_axes", check_collective_axes(body, axes)),
        ("wire_dtypes", check_wire_dtypes(codec, tree_flat_dim(params0))),
        ("no_f64", check_no_f64(body)),
    ]
    if execute:
        # run the compiled path for real so the retrace sentinel has a
        # compilation to watch and the trace above matches an executable
        out_state, metrics = rounds.scan_rounds(step, state, inputs)
        jax.block_until_ready(metrics["loss_est"])
    return [ContractViolation(name, check, d)
            for check, ds in details for d in ds]


def run_matrix(execute: bool = True) -> ContractReport:
    configs = matrix_configs()
    violations: list[ContractViolation] = []
    for cfg in configs:
        violations.extend(run_config(*cfg[:5], execute=execute))
    violations.extend(ContractViolation("obs/callback", "obs_tap", d)
                      for d in check_obs_tap())
    return ContractReport([c[0] for c in configs] + ["obs/callback"],
                          violations)
