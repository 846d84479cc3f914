"""obs/ subsystem invariants (DESIGN.md §13): an active MetricStream must be
a pure *observer* — trajectories and stacked (K,) histories bitwise-unchanged
versus ``obs=None`` — while every streamed row carries exactly the stacked
metric values (one float32 cast, both transports, both drivers, local and
sharded topologies). Plus the sink round-trips, the run manifest, eval-row
interleaving (and the no-silent-shadowing collision check in core/rounds),
the round driver's host spans and the phase vocabulary (obs/trace.py), and
the launch/feature_dist deprecation shims.

On a single-device run (tier-1 CI) the sharded case degenerates to one
shard; the multi-device CI job (XLA_FLAGS=--xla_force_host_platform_
device_count=8) runs the same tests with real client distribution.
"""
import ast
import glob
import json
import re
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import make_codec
from repro.configs.base import FLConfig
from repro.core import algorithms, fed
from repro.core import rounds as rounds_lib
from repro.core.topology import feature_sharded_for, sharded_for
from repro.models import mlp
from repro.obs import (CsvSink, JsonlSink, MemorySink, MetricStream,
                       StdoutSink)
from repro.obs import HostSpans
from repro.obs import sinks as obs_sinks
from repro.obs import trace as obs_trace

P, J, L = 12, 6, 3
I = 8                                   # sample clients; divisible by 1/2/4/8
K = 10                                  # rounds per run


def _fl(**kw):
    base = dict(batch_size=20, a1=0.9, a2=0.5, alpha_rho=0.1,
                alpha_gamma=0.6, tau=0.2)
    base.update(kw)
    return FLConfig(**base)


def _sample_data(key, n=240):
    z = jax.random.normal(key, (n, P))
    lab = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, L)
    return fed.partition_samples(z, jax.nn.one_hot(lab, L), I)


def _run_alg1(obs=None, driver="scan", topology=None, codec=None, rounds=K,
              eval_fn=None, eval_every=0):
    data = _sample_data(jax.random.PRNGKey(0))
    params0 = mlp.init(jax.random.PRNGKey(1), P, J, L)
    return algorithms.algorithm1(mlp.per_sample_loss, params0, data, _fl(),
                                 rounds, jax.random.PRNGKey(2),
                                 eval_fn=eval_fn, eval_every=eval_every,
                                 driver=driver, codec=codec,
                                 topology=topology, obs=obs)


def _run_alg3(obs=None, codec=None, topology=None, rounds=K):
    key = jax.random.PRNGKey(3)
    z = jax.random.normal(key, (200, P))
    lab = jax.random.randint(jax.random.fold_in(key, 1), (200,), 0, L)
    data = fed.partition_features(z, jax.nn.one_hot(lab, L), 4)
    params0 = {"w0": jax.random.normal(key, (L, J)) * 0.2,
               "blocks": jax.random.normal(jax.random.fold_in(key, 2),
                                           (4, J, P // 4)) * 0.2}
    return algorithms.algorithm3(mlp.per_sample_loss_from_h, mlp.client_h,
                                 params0, data, _fl(), rounds,
                                 jax.random.PRNGKey(4), eval_every=0,
                                 codec=codec, topology=topology, obs=obs)


def _assert_bitwise(a, b, what):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            f"{what} changed under an active stream"


def _assert_rows_match(rows, history, rounds, log_every=1):
    """Every streamed round row equals the f32-cast stacked history value."""
    round_rows = [r for r in rows if r["kind"] == "round"]
    expect_t = [t for t in range(1, rounds + 1) if t % log_every == 0]
    assert [r["t"] for r in round_rows] == expect_t
    names = [k for k in round_rows[0] if k not in ("kind", "t")]
    assert names, "round rows carry no metrics"
    for row in round_rows:
        for nm in names:
            want = float(np.float32(np.asarray(history["round_" + nm]
                                               [row["t"] - 1])))
            assert row[nm] == want, (nm, row["t"], row[nm], want)


# ---------------------------------------------------------------------------
# rows == stacked history, trajectories unchanged
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("driver,transport", [("scan", "future"),
                                              ("scan", "callback"),
                                              ("loop", "future")])
def test_stream_exact_and_pure(driver, transport):
    r_plain = _run_alg1(driver=driver)
    stream = MetricStream([MemorySink()], transport=transport)
    r_obs = _run_alg1(obs=stream, driver=driver)
    stream.sync()

    _assert_bitwise(r_plain.params, r_obs.params, "params")
    assert sorted(r_plain.history) == sorted(r_obs.history)
    for k in r_plain.history:
        _assert_bitwise(r_plain.history[k], r_obs.history[k],
                        f"history[{k!r}]")
    _assert_rows_match(stream.rows, r_plain.history, K)
    assert stream.rows == stream.sinks[0].rows


def test_stream_exact_sharded():
    topo = sharded_for(I)
    r_plain = _run_alg1(topology=topo)
    stream = MetricStream()
    r_obs = _run_alg1(obs=stream, topology=topo)
    stream.sync()
    _assert_bitwise(r_plain.params, r_obs.params, "params")
    _assert_rows_match(stream.rows, r_plain.history, K)


def test_log_every_thins_rows():
    stream = MetricStream(log_every=3)
    r = _run_alg1(obs=stream)
    stream.sync()
    _assert_rows_match(stream.rows, r.history, K, log_every=3)


def test_partial_flush_chunks():
    # flush_every that does not divide K: tail chunk still lands, in order
    stream = MetricStream(flush_every=7)
    r = _run_alg1(obs=stream, rounds=12)
    stream.sync()
    _assert_rows_match(stream.rows, r.history, 12)


def test_stream_with_codec_carries_ef_norm():
    codec = make_codec("int8")
    stream = MetricStream()
    _run_alg1(obs=stream, codec=codec)
    stream.sync()
    row = next(r for r in stream.rows if r["kind"] == "round")
    assert "ef_norm" in row and "stat_res" in row


def test_bad_transport_rejected():
    with pytest.raises(ValueError, match="transport"):
        MetricStream(transport="telegraph")


# ---------------------------------------------------------------------------
# feature (vertical) drivers
# ---------------------------------------------------------------------------


def test_feature_stream_exact_and_pure():
    r_plain = _run_alg3()
    stream = MetricStream()
    r_obs = _run_alg3(obs=stream)
    stream.sync()
    _assert_bitwise(r_plain.params, r_obs.params, "params")
    _assert_rows_match(stream.rows, r_plain.history, K)
    row = stream.rows[0]
    assert "stat_res" in row and "upload_bytes" in row


def test_feature_stream_sharded_with_codec():
    topo = feature_sharded_for(4)
    codec = make_codec("int8")
    stream = MetricStream()
    r = _run_alg3(obs=stream, codec=codec, topology=topo)
    stream.sync()
    _assert_rows_match(stream.rows, r.history, K)
    assert "ef_norm" in stream.rows[0]


# ---------------------------------------------------------------------------
# eval interleaving + the collision guard (core/rounds.py)
# ---------------------------------------------------------------------------


def test_eval_rows_interleaved_in_order():
    stream = MetricStream()
    _run_alg1(obs=stream, eval_fn=lambda p, s: {"test_acc": 0.5},
              eval_every=5)
    stream.sync()
    kinds_t = [(r["kind"], r["t"]) for r in stream.rows]
    # eval rows land right after their chunk's round rows, in t order
    assert kinds_t.index(("eval", 5)) == kinds_t.index(("round", 5)) + 1
    assert kinds_t.index(("eval", 10)) == kinds_t.index(("round", 10)) + 1
    evals = [r for r in stream.rows if r["kind"] == "eval"]
    assert [r["test_acc"] for r in evals] == [0.5, 0.5]


def test_eval_metric_collision_raises():
    # an eval hook must not silently overwrite a per-round scan series
    with pytest.raises(ValueError, match="round_loss_est"):
        _run_alg1(eval_fn=lambda p, s: {"round_loss_est": 0.0}, eval_every=5)
    with pytest.raises(ValueError, match="round"):
        _run_alg1(eval_fn=lambda p, s: {"round": 0.0}, eval_every=5)


def test_emit_event_direct_and_queued():
    stream = MetricStream([MemorySink()])
    stream.emit_event({"kind": "span", "span": "setup", "dur_s": 0.1})
    _run_alg1(obs=stream, rounds=3)
    stream.emit_event({"kind": "span", "span": "teardown", "dur_s": 0.2})
    stream.sync()
    kinds = [r["kind"] for r in stream.rows]
    assert kinds[0] == "span" and kinds[-1] == "span"
    assert kinds[1:-1] == ["round"] * 3


def test_host_spans_emit_rows_through_stream():
    stream = MetricStream([MemorySink()])
    spans = HostSpans(stream)
    with spans.span("dispatch", rounds=3, t0=1):
        pass
    stream.sync()
    (row,) = stream.rows
    assert row["kind"] == "span" and row["span"] == "dispatch"
    assert row["rounds"] == 3 and row["t0"] == 1 and row["dur_s"] >= 0
    assert not hasattr(spans, "spans")      # rows live in the stream only


# ---------------------------------------------------------------------------
# phase tracing: the round driver's host spans, the scope vocabulary
# ---------------------------------------------------------------------------


def test_run_rounds_driver_spans_in_order(tmp_path):
    # 2 chunks of 3 rounds with an eval hook, under the profiler
    jax.profiler.start_trace(str(tmp_path))
    try:
        _run_alg1(rounds=6, eval_fn=lambda p, s: {"test_acc": 0.5},
                  eval_every=3)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(path)
    lines = [[e for e in line.events
              if e.name in obs_trace.DRIVER_SPANS]
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines]
    lines = [evs for evs in lines if evs]
    assert len(lines) == 1, "driver spans split over host threads"
    evs = sorted(lines[0], key=lambda e: e.start_ns)
    inputs, launch, evals, history = obs_trace.DRIVER_SPANS
    assert [e.name for e in evs] == [inputs, launch, evals] * 2 + [history]
    assert [dict(e.stats) for e in evs if e.name == launch] == [
        {"rounds": 3, "t": 1}, {"rounds": 3, "t": 4}]


def _scope_literals():
    """Every string literal passed to `phase(...)`/`scoped(...)` (as a
    call or a decorator) under src/."""
    src = Path(obs_trace.__file__).resolve().parents[2]
    found = set()
    for path in src.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            fn = node.func
            name = fn.attr if isinstance(fn, ast.Attribute) else getattr(
                fn, "id", None)
            arg = node.args[0]
            if name in ("phase", "scoped") and isinstance(arg, ast.Constant) \
                    and isinstance(arg.value, str):
                found.add((arg.value, path.name))
    return found


def test_phase_literals_are_the_vocabulary():
    found = _scope_literals()
    stray = sorted((n, f) for n, f in found if n not in obs_trace.PHASES)
    assert not stray, f"scopes missing from obs.trace.PHASES: {stray}"
    # and PHASES names no scope the program has stopped using
    assert {n for n, _ in found} == set(obs_trace.PHASES)


def test_cohort_int8_step_hlo_upload_scopes():
    from repro.comm.error_feedback import CommCarry, ef_store_init
    from repro.core import optimizer

    data = _sample_data(jax.random.PRNGKey(0))
    params0 = mlp.init(jax.random.PRNGKey(1), P, J, L)
    fl = _fl()
    step = algorithms.make_algorithm1_step(
        mlp.per_sample_loss, data, fl, participation=4,
        codec=make_codec("int8"), cohort=True)
    dim = sum(x.size for x in jax.tree.leaves(params0))
    state = CommCarry(opt=optimizer.ssca_init(params0),
                      ef=ef_store_init(I, dim))
    inputs = rounds_lib.make_inputs(fl, 1, 2, jax.random.PRNGKey(2))
    hlo = rounds_lib._scan_jit(step).lower(state, inputs).as_text(
        "hlo", debug_info=True)
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ("ef-gather", "ef-scatter", "round-metrics"):
        assert any(f"round/{scope}/" in n for n in names), scope
    # the new scopes sit beside codec-encode, never inside it, so the
    # encode's device time keeps exactly its own ops
    inside = [n for n in names if re.search(
        r"/codec-encode/(.*/)?(ef-gather|ef-scatter|round-metrics)/", n)]
    assert not inside, inside[:3]


# ---------------------------------------------------------------------------
# sinks + manifest
# ---------------------------------------------------------------------------


def test_jsonl_sink_roundtrip(tmp_path):
    path = str(tmp_path / "rows.jsonl")
    stream = MetricStream([JsonlSink(path)])
    _run_alg1(obs=stream, rounds=4)
    stream.close()
    with open(path) as f:
        disk = [json.loads(line) for line in f]
    assert disk == stream.rows


def test_csv_and_stdout_sinks(tmp_path, capsys):
    path = str(tmp_path / "rows.csv")
    stream = MetricStream([CsvSink(path), StdoutSink(prefix="obs ")])
    _run_alg1(obs=stream, rounds=3)
    stream.close()
    lines = open(path).read().splitlines()
    assert len(lines) == 4 and "loss_est" in lines[0]   # header + 3 rows
    out = capsys.readouterr().out
    assert out.count("obs ") == 3 and "loss_est=" in out


def test_run_manifest_contents(tmp_path):
    path = str(tmp_path / "m.json")
    obs_sinks.write_manifest(path, config=_fl(), codec=make_codec("int8"),
                             topology=sharded_for(I),
                             cost={"flops": 123, "bytes": 456})
    man = json.load(open(path))
    assert man["codec"] == "int8"
    assert man["jax_version"] == jax.__version__
    assert man["hlo_cost"] == {"flops": 123, "bytes": 456}
    assert man["config"]["batch_size"] == 20
    assert man["topology"]["name"] == "sharded"
    assert man["topology"]["num_shards"] >= 1
    assert isinstance(man["git_sha"], str)


# ---------------------------------------------------------------------------
# launch/feature_dist deprecation shims
# ---------------------------------------------------------------------------


def test_feature_dist_deprecation_warns_once():
    from repro.launch import feature_dist
    from repro.launch.mesh import make_feature_mesh

    mesh = make_feature_mesh(1)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        feature_dist.make_feature_round(mesh, mlp.per_sample_loss_from_h,
                                        mlp.client_h)
    dep = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 1
    assert "--mode feature" in str(dep[0].message)
    assert "make_feature_round" in str(dep[0].message)
    # the shim message carries the lint rule code so the runtime warning
    # and `python -m repro.analysis` point at the same rule
    assert str(dep[0].message).startswith("[FLT004]")


class _FailingSink(MemorySink):
    """Accepts the first row, then fails the way a full disk would."""

    def __init__(self):
        super().__init__()
        self.closed = False

    def emit(self, row):
        if self.rows:
            raise OSError("sink cannot write")
        super().emit(row)

    def close(self):
        self.closed = True


@pytest.mark.parametrize("transport", ["future", "callback"])
def test_drainer_failure_raised_by_sync_and_close(transport):
    sink = _FailingSink()
    stream = MetricStream([sink], transport=transport)
    _run_alg1(obs=stream)
    with pytest.raises(RuntimeError, match="drainer failed") as err:
        stream.sync()
    assert isinstance(err.value.__cause__, OSError)
    with pytest.raises(RuntimeError, match="drainer failed"):
        stream.close()
    assert sink.closed
