"""Two additions to the trace reduction of ``bench/lib/trace.py``, kept in a
file of their own so that reduction stays as the accepted benchmark reads
it (PERF.md §7 says how ``bench/run.py`` would take them up):

* ``scopes_from_hlo``: the TPU compiler expands some ops into loops of its
  own (the EF store's scatter becomes a ``while`` of ``dynamic-update-
  slice``s) whose body ops carry no ``op_name``. Such an op takes the scope
  of the innermost ``while``, ``call`` or ``conditional`` whose called
  computation holds it.
* ``span_gaps``: each idle gap of the device put down to the innermost of
  the program's own host spans (``core/rounds.run_rounds``: ``rounds/*``)
  over its middle, with the count of each span and of the executables
  launched under it.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import re
from collections import defaultdict

from bench.lib import trace as tr

# the program's scopes that bench.run.SCOPES does not name yet
SCOPES = ("ef-gather", "ef-scatter", "round-metrics")
SPAN_PREFIX = "rounds/"
# the host event of one executable's launch
LAUNCH = "PJRT_LoadedExecutable_Execute"

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(\S+) .*\{\s*$")
_CALLEE = re.compile(r"\b(?:body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([^\s,}]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")


def scopes_from_hlo(hlo_text: str, scopes) -> tuple[str, dict]:
    """``trace.scopes_from_hlo``, where an instruction with no ``op_name``
    in a computation that a container op calls takes that container's
    scope (and a container with no ``op_name`` its own container's)."""
    module, out = tr.scopes_from_hlo(hlo_text, scopes)
    computation = None
    bare = {}                 # instruction with no op_name -> its computation
    caller = {}               # called computation -> container instruction
    for line in hlo_text.splitlines():
        if not line[:1].isspace():
            m = _COMPUTATION.match(line)
            computation = m.group(1) if m else None
            continue
        instr = line.strip().removeprefix("ROOT ")
        name_m = tr._INSTR.match(instr)
        if not name_m:
            continue
        name = name_m.group(1)
        op = tr._OPCODE.match(instr)
        if op and op.group(1) in tr.CONTAINERS:
            callees = _CALLEE.findall(instr)
            for group in _BRANCHES.findall(instr):
                callees += [c.strip().lstrip("%") for c in group.split(",")]
            for c in callees:
                caller[c] = name
        if name not in out:
            bare[name] = computation

    def container_scope(name):
        host = caller.get(bare[name])
        if host in out:
            return out[host]
        return container_scope(host) if host in bare else None

    for name in bare:
        scope = container_scope(name)
        if scope is not None:
            out[name] = scope
    return module, out


def span_gaps(pd, window, anchor: str, prefix: str = SPAN_PREFIX) -> dict:
    """Over ``window`` of ``pd``, on the host thread that carries ``anchor``
    (as ``trace.reduce_trace`` reads them)::

        span_gaps      {innermost span named ``prefix``* over the gap's
                       middle, or 'none': idle seconds}, summed over chips
        span_count     {span: events that start in the window}
        span_launches  {innermost span over the launch, or 'none':
                       executables launched in the window}
    """
    lo, hi = window
    host = [(e.start_ns, e.end_ns, e.name)
            for line in tr._anchor_lines(pd, anchor) for e in line.events]
    spans = [h for h in host if h[2].startswith(prefix)]
    gaps = defaultdict(float)
    for plane in tr.device_planes(pd):
        ivs = [(e.start_ns, e.end_ns) for line in plane.lines
               if line.name == "XLA Ops" for e in line.events]
        merged = tr._union(tr._clip(ivs, lo, hi))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[_innermost(spans, 0.5 * (a + b))] += (b - a) * 1e-9
    count, launches = defaultdict(int), defaultdict(int)
    for s, _, name in spans:
        if lo <= s <= hi:
            count[name] += 1
    for s, _, name in host:
        if name.startswith(LAUNCH) and lo <= s <= hi:
            launches[_innermost(spans, s)] += 1
    return {"span_gaps": dict(gaps), "span_count": dict(count),
            "span_launches": dict(launches)}


def _innermost(events, t):
    """Name of the shortest of ``events`` (start, end, name) that covers
    ``t``, or 'none'."""
    owners = [(e - s, n) for s, e, n in events if s <= t <= e]
    return min(owners)[1] if owners else "none"
