"""Self-test of bench/lib/spans.py: the container rule on a synthetic HLO,
idle gaps put down to the program's ``rounds/*`` spans on a synthetic trace
with known intervals, and the small trace recorded on a TPU v5e chip
(bench/tests/data).

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench.lib import spans
from bench.lib import trace as tr

DATA = Path(__file__).resolve().parent / "data"
KNOWN = ("round", "client-compute", "ef-scatter")

# a scatter expanded into a while whose body ops carry no metadata, one of
# them inside a nested while that carries none either; an entry op with no
# metadata and no container stays out of the map (unscoped)
HLO = '''HloModule jit_step, entry_computation_layout={()->()}

%inner (q: (s32[])) -> (s32[]) {
  %q = (s32[]) parameter(0)
  ROOT %add.7 = s32[] add(s32[] %x, s32[] %y)
}

%body (p: (s32[])) -> (s32[]) {
  %p = (s32[]) parameter(0)
  %dynamic-update-slice.3 = f32[8,4]{1,0} dynamic-update-slice(f32[8,4]{1,0} %a, f32[1,4]{1,0} %u, s32[] %i, s32[] %j)
  %while.9 = (s32[]) while((s32[]) %p), condition=%cond, body=%inner
  ROOT %tuple.1 = (s32[]) tuple(s32[] %k)
}

%cond (c: (s32[])) -> pred[] {
  %c = (s32[]) parameter(0)
  ROOT %compare.2 = pred[] compare(s32[] %k, s32[] %n), direction=LT
}

ENTRY %main (a: f32[8,4]) -> f32[8,4] {
  %a = f32[8,4]{1,0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%f, metadata={op_name="jit(step)/round/client-compute/dot_general"}
  %while.4 = (s32[]) while((s32[]) %t), condition=%cond, body=%body, metadata={op_name="jit(step)/round/ef-scatter/scatter"}
  ROOT %copy.5 = f32[8,4]{1,0} copy(f32[8,4]{1,0} %a)
}
'''

# one chip, window [0, 60) us: ops at [0, 10), [20, 25), [26, 29), [45, 50);
# on the dispatch thread the program's spans rounds/chunk [8, 48) over
# rounds/inputs [10, 18) (over a JAX event [11, 17)), rounds/launch
# [18, 22) (over an executable launch at 19) and rounds/eval [30, 45);
# rounds/history [45, 58)
SYNTH = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 0 duration_ps: 60000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 5000000 }
    events { metadata_id: 1 offset_ps: 26000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 45000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop" } }
  event_metadata { key: 10 value { id: 10 name: "jit_step(123)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 60000000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 40000000 }
    events { metadata_id: 3 offset_ps: 10000000 duration_ps: 8000000 }
    events { metadata_id: 4 offset_ps: 11000000 duration_ps: 6000000 }
    events { metadata_id: 5 offset_ps: 18000000 duration_ps: 4000000 }
    events { metadata_id: 6 offset_ps: 19000000 duration_ps: 1000000 }
    events { metadata_id: 7 offset_ps: 30000000 duration_ps: 15000000 }
    events { metadata_id: 8 offset_ps: 45000000 duration_ps: 13000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench/dispatch" } }
  event_metadata { key: 2 value { id: 2 name: "rounds/chunk" } }
  event_metadata { key: 3 value { id: 3 name: "rounds/inputs" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(add)" } }
  event_metadata { key: 5 value { id: 5 name: "rounds/launch" } }
  event_metadata { key: 6 value { id: 6 name: "PJRT_LoadedExecutable_Execute linkage" } }
  event_metadata { key: 7 value { id: 7 name: "rounds/eval" } }
  event_metadata { key: 8 value { id: 8 name: "rounds/history" } }
}
'''


def test_container_scope_for_ops_without_metadata():
    module, scopes = spans.scopes_from_hlo(HLO, KNOWN)
    _, plain = tr.scopes_from_hlo(HLO, KNOWN)
    assert module == "jit_step"
    assert plain == {"fusion.1": "client-compute", "while.4": "ef-scatter"}
    assert scopes == dict(plain, **{
        "p": "ef-scatter", "dynamic-update-slice.3": "ef-scatter",
        "while.9": "ef-scatter", "tuple.1": "ef-scatter",
        "q": "ef-scatter", "add.7": "ef-scatter",
        "c": "ef-scatter", "compare.2": "ef-scatter"})


def test_span_gaps_go_to_the_innermost_program_span():
    pd = ProfileData.from_text_proto(SYNTH)
    window = tr.host_window(pd, "bench/dispatch")
    red = tr.reduce_trace(pd, window, dict([tr.scopes_from_hlo(HLO, KNOWN)]),
                          "bench/dispatch")
    out = spans.span_gaps(pd, window, "bench/dispatch")
    # [10, 20) under inputs (not the JAX event inside it), [25, 26) under
    # chunk alone, [29, 45) under eval, [50, 60) under history
    assert out["span_gaps"] == {"rounds/inputs": pytest.approx(10e-6),
                                "rounds/chunk": pytest.approx(1e-6),
                                "rounds/eval": pytest.approx(16e-6),
                                "rounds/history": pytest.approx(10e-6)}
    idle = red["window_s"] - red["busy_s"]
    assert sum(out["span_gaps"].values()) == pytest.approx(idle)
    assert out["span_count"] == {"rounds/chunk": 1, "rounds/inputs": 1,
                                 "rounds/launch": 1, "rounds/eval": 1,
                                 "rounds/history": 1}
    assert out["span_launches"] == {"rounds/launch": 1}


def test_recorded_chip_trace_unchanged_by_the_additions():
    """The recorded dispatch (a program with no ``rounds/*`` spans yet)
    reduces to the same busy, scope and gap times under either scope map,
    and its idle time all falls outside any program span."""
    from bench.run import SCOPES
    hlo = (DATA / "module0.hlo.txt").read_text()
    pd = ProfileData.from_file(str(DATA / "trace.xplane.pb"))
    window = tr.host_window(pd, "bench/dispatch")
    reds = [tr.reduce_trace(pd, window, dict([scopes(hlo, SCOPES)]),
                            "bench/dispatch")
            for scopes in (tr.scopes_from_hlo, spans.scopes_from_hlo)]
    for key in ("busy_s", "scope_s", "gaps"):
        assert reds[0][key] == reds[1][key], key
    out = spans.span_gaps(pd, window, "bench/dispatch")
    idle = reds[0]["window_s"] - reds[0]["busy_s"]
    assert list(out["span_gaps"]) == ["none"]
    assert out["span_gaps"]["none"] == pytest.approx(idle, rel=1e-9)
    assert out["span_count"] == {}
    # the dispatch launched 43 executables: the scan and 42 eager ops
    assert out["span_launches"] == {"none": 43}


def test_scopes_cover_the_program_vocabulary():
    from bench.run import SCOPES
    from repro.obs.trace import PHASES
    assert set(PHASES) <= set(SCOPES) | set(spans.SCOPES)
