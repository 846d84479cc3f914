"""Tests for the static-analysis subsystem (DESIGN.md §16): each FLT rule
against its committed bad/clean fixture pair, suppression comments, CLI
exit codes and JSON report, the jaxpr contract checkers (positive run over
a slice of the config matrix + synthetic negative controls per checker),
and the retrace sentinel (clean reuse vs a provoked recompile).

The FULL 16-config contract matrix runs in CI via
`python -m repro.analysis` (the analysis job) — here we keep a
representative 4-config diagonal so tier-1 stays fast."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import contracts, lint, retrace
from repro.analysis.__main__ import main as analysis_main
from repro.configs.base import FLConfig
from repro.core import rounds

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "analysis")

RULE_CODES = ("FLT001", "FLT002", "FLT003", "FLT004", "FLT005", "FLT006")


def _lint_fixture(name):
    return lint.lint_paths([os.path.join(FIXTURES, name)], root=REPO)


# ---------------------------------------------------------------------------
# layer 1: the lint rules, fixture pair per rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("code", RULE_CODES)
def test_rule_flags_bad_fixture(code):
    res = _lint_fixture(f"{code.lower()}_bad.py")
    assert res.exit_code == 1
    codes = {f.code for f in res.findings}
    assert codes == {code}, f"expected only {code}, got {codes}"


@pytest.mark.parametrize("code", RULE_CODES)
def test_rule_passes_clean_twin(code):
    res = _lint_fixture(f"{code.lower()}_clean.py")
    assert res.exit_code == 0, [f.render() for f in res.findings]


def test_bad_fixtures_flag_expected_lines():
    res = _lint_fixture("flt002_bad.py")
    lines = sorted(f.line for f in res.findings)
    assert len(lines) == 3          # straight-line, loop, positional split
    msgs = " ".join(f.message for f in res.findings)
    assert "fold_in the loop index" in msgs
    assert "client_keys" in msgs


def test_suppression_comment(tmp_path):
    bad = open(os.path.join(FIXTURES, "flt001_bad.py")).read()
    patched = bad.replace(".item()             #", ".item()  # flint: disable=FLT001 #")
    p = tmp_path / "suppressed.py"
    p.write_text(patched)
    res = lint.lint_paths([p], root=tmp_path)
    assert all(f.line != 9 for f in res.findings if f.code == "FLT001")
    assert any(s.line == 9 and s.code == "FLT001" and s.suppressed
               for s in res.suppressed)


def test_suppression_without_code_disables_all(tmp_path):
    p = tmp_path / "all_off.py"
    p.write_text(
        "import jax, time\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x * time.time()  # flint: disable\n")
    res = lint.lint_paths([p], root=tmp_path)
    assert res.exit_code == 0
    assert len(res.suppressed) == 1


def test_repo_is_lint_clean_with_zero_core_suppressions():
    res = lint.lint_paths([os.path.join(REPO, "src", "repro"),
                           os.path.join(REPO, "benchmarks")], root=REPO)
    assert res.exit_code == 0, "\n".join(f.render() for f in res.findings)
    core = os.path.join("src", "repro", "core")
    core_suppressed = [s for s in res.suppressed if core in s.path]
    assert not core_suppressed, (
        "src/repro/core must pass with zero suppressions: "
        + "\n".join(s.render() for s in core_suppressed))


def test_reachability_does_not_flag_host_code():
    # obs/sinks host-side .item() and benchmark timing loops must NOT flag:
    # they are never passed to a jit entry
    res = lint.lint_paths([os.path.join(REPO, "src", "repro", "obs"),
                           os.path.join(REPO, "benchmarks")], root=REPO)
    assert not [f for f in res.findings if f.code in ("FLT001", "FLT003")]


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_exit_codes_per_fixture():
    for code in RULE_CODES:
        bad = os.path.join(FIXTURES, f"{code.lower()}_bad.py")
        clean = os.path.join(FIXTURES, f"{code.lower()}_clean.py")
        assert analysis_main([bad]) == 1
        assert analysis_main([clean]) == 0


def test_cli_json_report(tmp_path, capsys):
    out = tmp_path / "report.json"
    rc = analysis_main([os.path.join(FIXTURES, "flt004_bad.py"),
                        "--format", "json", "-o", str(out)])
    assert rc == 1
    report = json.loads(out.read_text())
    assert report["tool"] == "repro.analysis"
    assert report["lint"]["num_findings"] > 0
    assert all(f["code"] == "FLT004" for f in report["lint"]["findings"])
    # explicit paths skip the contract matrix
    assert report["contracts"] is None


# ---------------------------------------------------------------------------
# layer 2: jaxpr contract checkers
# ---------------------------------------------------------------------------

# diagonal through the matrix: every engine/topology/codec/dp value appears
_DIAGONAL = [
    ("dense/local/identity/nodp", "dense", "local", "identity", False),
    ("dense/sharded/int8/dp", "dense", "sharded", "int8", True),
    ("cohort/local/int8/nodp", "cohort", "local", "int8", False),
    ("cohort/sharded/identity/dp", "cohort", "sharded", "identity", True),
]


@pytest.mark.parametrize("cfg", _DIAGONAL, ids=[c[0] for c in _DIAGONAL])
def test_contract_config_passes(cfg):
    violations = contracts.run_config(*cfg, execute=False)
    assert not violations, "\n".join(v.render() for v in violations)


def test_contract_matrix_covers_full_product():
    names = [c[0] for c in contracts.matrix_configs()]
    assert len(names) == 16
    assert len(set(names)) == 16
    for engine in ("dense", "cohort"):
        for topo in ("local", "sharded"):
            for codec in ("identity", "int8"):
                for dp in ("dp", "nodp"):
                    assert f"{engine}/{topo}/{codec}/{dp}" in names


def test_obs_tap_contract():
    assert contracts.check_obs_tap() == []


def test_scan_pure_catches_callback():
    def tap(x):
        return None

    def body(c, x):
        jax.experimental.io_callback(tap, None, x, ordered=False)
        return c + x, x

    closed = jax.make_jaxpr(
        lambda c, xs: jax.lax.scan(body, c, xs))(
            jnp.zeros(()), jnp.arange(3.0))
    body_jaxpr = contracts.find_scan_body(closed)
    out = contracts.check_scan_pure(body_jaxpr)
    assert out and "io_callback" in out[0]


def test_dp_before_encode_catches_swapped_order():
    # encode-then-noise: the int8 convert appears BEFORE the gaussian draw
    def body(c, key):
        enc = (c * 127.0).astype(jnp.int8)
        noisy = enc.astype(jnp.float32) + jax.random.normal(key, c.shape)
        return noisy, enc

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    closed = jax.make_jaxpr(
        lambda c, ks: jax.lax.scan(body, c, ks))(jnp.zeros((4,)), keys)
    body_jaxpr = contracts.find_scan_body(closed)
    out = contracts.check_dp_before_encode(body_jaxpr, dp_on=True, int8=True)
    assert out and "does not precede" in out[0]


def test_dp_before_encode_catches_missing_and_spurious_noise():
    def pure_body(c, x):
        return c + x, x

    closed = jax.make_jaxpr(
        lambda c, xs: jax.lax.scan(pure_body, c, xs))(
            jnp.zeros(()), jnp.arange(3.0))
    body_jaxpr = contracts.find_scan_body(closed)
    assert contracts.check_dp_before_encode(body_jaxpr, dp_on=True,
                                            int8=False)
    assert not contracts.check_dp_before_encode(body_jaxpr, dp_on=False,
                                                int8=False)


@pytest.mark.parametrize("check_vma", [True, False])
def test_collective_axes_catches_undeclared_axis(check_vma):
    """shard_map binds psum_invariant under check_vma, plain psum without:
    the contract must see both."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_client_mesh

    mesh = make_client_mesh(axis="data")

    def summed(x):
        return jax.lax.psum(x, "data")

    fn = jax.shard_map(summed, mesh=mesh, in_specs=P("data"), out_specs=P(),
                       check_vma=check_vma)
    closed = jax.make_jaxpr(fn)(jnp.zeros((jax.device_count(),)))
    assert contracts.check_collective_axes(closed.jaxpr, allowed=())
    assert not contracts.check_collective_axes(closed.jaxpr,
                                               allowed=("data",))


def test_wire_dtypes_catches_spec_violation():
    from repro.comm.codecs import QuantEncoded

    class BadCodec:
        def encode(self, x, key=None):
            # values must be int8 per the quantizer wire spec
            return QuantEncoded(values=x, scales=jnp.ones((1,), jnp.float32))

    out = contracts.check_wire_dtypes(BadCodec(), dim=8)
    assert out and "int8" in out[0]

    from repro.comm.codecs import make_codec
    assert contracts.check_wire_dtypes(make_codec("int8"), dim=256) == []
    assert contracts.check_wire_dtypes(make_codec("identity"), dim=8) == []
    assert contracts.check_wire_dtypes(None, dim=8) == []


# ---------------------------------------------------------------------------
# retrace sentinel
# ---------------------------------------------------------------------------


def _toy_step():
    def step(state, inp):
        return state + inp.rho, {"m": state}
    return step


def test_retrace_sentinel_clean_on_stable_shapes():
    fl = FLConfig()
    step = _toy_step()
    state = jnp.zeros(())
    inputs = rounds.make_inputs(fl, 1, 4, jax.random.PRNGKey(0))
    with retrace.RetraceSentinel() as sentinel:
        rounds.scan_rounds(step, state, inputs)
        rounds.scan_rounds(step, state, inputs)   # cache hit, no retrace
    assert sentinel.ok, sentinel.render_text()
    assert sentinel.report()["tracked"] == 1


def test_retrace_sentinel_catches_deliberate_recompile():
    fl = FLConfig()
    step = _toy_step()
    state = jnp.zeros(())
    with retrace.RetraceSentinel() as sentinel:
        # same step fn, different K -> different input shapes -> retrace
        rounds.scan_rounds(step, state,
                           rounds.make_inputs(fl, 1, 4, jax.random.PRNGKey(0)))
        rounds.scan_rounds(step, state,
                           rounds.make_inputs(fl, 1, 5, jax.random.PRNGKey(0)))
    assert not sentinel.ok
    assert sentinel.violations[0].compiles == 2
    assert "retrace" in sentinel.render_text()


def test_retrace_sentinel_restores_patches():
    orig_scan, orig_step = rounds._scan_jit, rounds._step_jit
    with retrace.RetraceSentinel():
        assert rounds._scan_jit is not orig_scan
    assert rounds._scan_jit is orig_scan
    assert rounds._step_jit is orig_step
