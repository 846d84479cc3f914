"""The correctness check catches the faults a cell can have. Each test
skips the harness's look for a chip, drives the rest of a run
(``run.run_once``: set-up, window, peak memory, reference) at a size a CPU
holds, with the program broken underneath, and sees ``correct`` come out
false; the unbroken run comes out true:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.lib.common import BENCH, peaks_for

jax.config.update("jax_enable_compilation_cache", False)


def tiny(cell):
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    w = {x["name"]: x for x in spec["workloads"]}[cell]
    tr = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    cfg = json.loads((BENCH / "configs" / f"{w['config']}.json").read_text())
    cfg.update(num_features=16, hidden=8, num_classes=4)
    tr.update(population=40, participation=8, batch=4, rounds_per_dispatch=3,
              eval_clients=8)
    return cell, cfg, tr, jax.devices()[:w["chips"]]


def drive(cell):
    name, cfg, tr, devices = tiny(cell)
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    result, checks = run.run_once(name, cfg, tr, e2e, [], 1234, 0.2, 0,
                                  devices, peaks_for("TPU v5 lite"))
    return result


CELLS = [w["name"] for w in json.loads(
    (BENCH.parent / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_unbroken_run_is_correct(cell):
    r = drive(cell)
    assert r["correct"], r["checks"]


def _state_unchanged(monkeypatch, cell):
    from repro.core import optimizer
    monkeypatch.setattr(optimizer, "ssca_step",
                        lambda state, *a, **k: state._replace(t=state.t + 1))


def _rows_fault(monkeypatch, cell, how):
    """Break the per-row losses where the program produces them: 'half'
    leaves out the second half of each batch and doubles the rest (the
    mean over what is left); 'altered' doubles one row's loss."""
    from repro.models import mlp

    def bend(per_row):
        b = per_row.shape[0]
        if how == "half":
            return per_row * 2.0 * (jnp.arange(b) < b // 2)
        return per_row.at[0].multiply(2.0)

    psl = mlp.per_sample_loss
    monkeypatch.setattr(mlp, "per_sample_loss",
                        lambda p, z, y: bend(psl(p, z, y)))


FAULTS = [(c, f) for c in CELLS
          for f in ("state_unchanged", "half_batch", "altered")]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_makes_run_incorrect(monkeypatch, cell, fault):
    tiny(cell)
    if fault == "state_unchanged":
        _state_unchanged(monkeypatch, cell)
    elif fault == "half_batch":
        _rows_fault(monkeypatch, cell, "half")
    else:
        _rows_fault(monkeypatch, cell, "altered")
    r = drive(cell)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    """The reference one precision lower (bfloat16 operands), put in the
    program's place, fails at least one of the cell's limits."""
    import importlib

    from bench.lib.common import compare
    name, cfg, tr, devices = tiny(cell)
    engine = importlib.import_module(f"bench.engines.{tr['engine']}")
    eng = engine.Engine(cfg, tr, 99, devices, lambda m: None)
    eng.free()
    ref = eng.reference()
    checks = compare(eng.reference("bf16"), ref, tr["limits"])
    assert any(v > lim for _, v, lim, _ in checks), checks


def test_reference_population_makes_the_program_rows():
    """The reference's own population copy makes, bit for bit, the rows,
    shard sizes and total of the program's VirtualFedData."""
    from bench.engines.cohort import population_key
    from bench.reference.population import Population
    from repro.data.synthetic import VirtualFedData

    prog = VirtualFedData(population_key(), 40, num_features=16,
                          num_classes=4, noise=4.0)
    ref = Population(population_key(), 40, 16, 4)
    ids = jnp.array([0, 7, 39], jnp.int32)
    idx = jnp.array([[0, 3, 31], [1, 1, 2], [5, 8, 30]], jnp.int32)
    assert prog.total == ref.total
    assert (prog.counts_for(ids) == ref.counts_for(ids)).all()
    for a, b in zip(prog.batch_rows(ids, idx), ref.batch_rows(ids, idx)):
        assert (a == b).all()
