"""Self-test of the trace reduction: a synthetic trace with known intervals,
and a small trace recorded on a TPU v5e chip (bench/tests/data).

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench.lib import trace as tr

DATA = Path(__file__).resolve().parent / "data"

HLO = '''HloModule jit_step, entry_computation_layout={()->()}
%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%f, metadata={op_name="jit(step)/round/client-compute/dot_general"}
%fusion.2 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%g, metadata={op_name="jit(step)/round/surrogate-solve/mul"}
%while.3 = (s32[]) while((s32[]) %t), body=%b, metadata={op_name="jit(step)/round/while"}
'''

# one chip: a module run of 100 us; ops at [0, 10) fusion.1, [5, 15) while.3
# (a container spanning fusion.2 at [6, 9)), [20, 25) fusion.2; host
# annotations: bench/dispatch over [0, 40) and bench/host-read over [15, 20)
SYNTH = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 0 duration_ps: 100000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "%while.3 = (s32[]) while((s32[]) %t), body=%b" } }
  event_metadata { key: 10 value { id: 10 name: "jit_step(123)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000000 }
    events { metadata_id: 2 offset_ps: 15000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench/dispatch" } }
  event_metadata { key: 2 value { id: 2 name: "bench/host-read" } }
}
'''


def test_scopes_from_hlo():
    module, scopes = tr.scopes_from_hlo(HLO, ("round", "client-compute",
                                              "surrogate-solve"))
    assert module == "jit_step"
    assert scopes == {"fusion.1": "client-compute",
                      "fusion.2": "surrogate-solve", "while.3": "round"}


def test_synthetic_trace_reduces_exactly():
    pd = ProfileData.from_text_proto(SYNTH)
    window = tr.host_window(pd, "bench/dispatch")
    assert window == (0, 40_000)
    mods = dict([tr.scopes_from_hlo(HLO, ("round", "client-compute",
                                          "surrogate-solve"))])
    red = tr.reduce_trace(pd, window, mods, "bench/dispatch")
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx(40e-6)
    # busy = union of [0, 15) and [20, 25)
    assert red["busy_s"] == pytest.approx(20e-6)
    # leaf ops only: the while's span is not counted a second time
    assert red["scope_s"][0] == pytest.approx(
        {"client-compute": 10e-6, "surrogate-solve": 8e-6})
    # gaps: [15, 20) under host-read, [25, 40) under dispatch
    assert red["gaps"] == [("bench/dispatch", pytest.approx(15e-6)),
                           ("bench/host-read", pytest.approx(5e-6))]
    bd = tr.breakdown(red)
    assert bd["device_ops"][0] == ["client-compute/fusion.1",
                                   pytest.approx(10e-6)]


def test_recorded_chip_trace():
    """One cohort-dense dispatch cut from a TPU v5e trace (the second
    dispatch of a ``--trace 1`` run, device ops and the annotated host
    thread; the HLO text keeps the lines of the ops that ran): busy fits
    the window, scope time fits busy, the idle gaps add up to the rest."""
    hlo = (DATA / "module0.hlo.txt").read_text()
    pd = ProfileData.from_file(str(DATA / "trace.xplane.pb"))
    from bench.run import SCOPES
    mods = dict([tr.scopes_from_hlo(hlo, SCOPES)])
    red = tr.reduce_trace(pd, tr.host_window(pd, "bench/dispatch"), mods,
                          "bench/dispatch")
    assert red["chips"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    scopes = red["scope_s"][0]
    assert sum(scopes.values()) <= red["busy_s"] * (1 + 1e-9)
    # the SSCA update of this small model is fused into ops of other
    # scopes: only these three carry time of their own here
    for s in ("client-compute", "batch-select", "cohort-select"):
        assert scopes.get(s, 0) > 0, (s, scopes)
    idle = red["window_s"] - red["busy_s"]
    assert sum(g for _, g in red["gaps"]) == pytest.approx(idle, rel=1e-6)
