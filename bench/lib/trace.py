"""Reduction of a profiler trace (``.xplane.pb``) to per-scope device time,
device busy and idle time, and idle gaps named by the host annotation
they fall in.

How a TPU trace looks (read by hand from a v5e trace): each chip is a plane
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per executable
run, named ``<module>(<fingerprint>)``) and ``XLA Ops`` (one event per HLO
instruction run, named by the instruction's text ``%fusion.12 = ...``). The
events carry no op metadata, so an op's named scope is read from the
compiled module's HLO text (``metadata={op_name="jit(..)/round/..."}``).
A ``while`` op's event spans the ops of its body, which have events of
their own: busy time is the union of all op intervals, and scope time sums
the leaf ops only. Host annotations (``jax.profiler.TraceAnnotation``) are
events of the host plane ``/host:CPU`` on the same clock.
"""
from __future__ import annotations

import re
from collections import defaultdict

CONTAINERS = ("while", "conditional", "call")
_INSTR = re.compile(r"^%?(\S+) = ")
_OPCODE = re.compile(r"^%?\S+ = (?:\(.*?\)|\S+) ([\w\-]+)\(")
_HLO_LINE = re.compile(r"^\s*(?:ROOT )?%?(\S+) = .*?metadata=\{op_name=\"([^\"]*)\"")
_MODULE = re.compile(r"^HloModule (\S+?),")


def scopes_from_hlo(hlo_text: str, scopes) -> tuple[str, dict]:
    """(module name, {instruction name: innermost known scope}) of one
    compiled module's HLO text. Instructions under no known scope map to
    'unscoped'."""
    module = None
    out = {}
    known = set(scopes)
    for line in hlo_text.splitlines():
        if module is None:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _HLO_LINE.match(line)
        if not m:
            continue
        parts = m.group(2).split("/")
        inner = [p for p in parts if p in known]
        out[m.group(1)] = inner[-1] if inner else "unscoped"
    return module, out


def _union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def device_planes(pd):
    return sorted((p for p in pd.planes if p.name.startswith("/device:TPU:")),
                  key=lambda p: p.name)


def reduce_trace(pd, window, modules: dict, anchor: str) -> dict:
    """Reduce ``pd`` (a ``jax.profiler.ProfileData``) over ``window``
    (start_ns, end_ns on the trace's clock).

    ``modules`` maps module names to their {instruction: scope} maps
    (``scopes_from_hlo``); ops of other modules count under
    ``other:<module>``. An idle gap is owned by the innermost host event
    that covers its middle, among the events of the host thread that
    carries the ``anchor`` annotation (the harness's own annotations and
    JAX's, such as ``PjitFunction(matmul)`` for an eager op's dispatch).

    Returns, averaged over the traced chips where it says so::

        window_s       length of the window
        busy_s         union of op intervals, averaged over chips
        scope_s        {scope: leaf-op seconds}, per chip (list)
        op_s           {(scope, instruction): seconds}, summed over chips
        gaps           [(owning host event or 'none', seconds)], summed by
                       owner over chips, longest first
        chips          number of device planes
    """
    lo, hi = window
    planes = device_planes(pd)
    busy, scope_s, op_s = [], [], defaultdict(float)
    host = [(e.start_ns, e.end_ns, e.name)
            for line in _anchor_lines(pd, anchor) for e in line.events]
    gap_s = defaultdict(float)
    for plane in planes:
        lines = {l.name: l for l in plane.lines}
        runs = sorted((e.start_ns, e.end_ns, e.name.split("(")[0])
                      for e in lines["XLA Modules"].events) \
            if "XLA Modules" in lines else []
        per_scope = defaultdict(float)
        ivs = []
        run_i = 0
        for e in sorted(lines["XLA Ops"].events, key=lambda e: e.start_ns) \
                if "XLA Ops" in lines else []:
            s, t = e.start_ns, e.end_ns
            if t <= lo or s >= hi:
                continue
            ivs.append((s, t))
            while run_i < len(runs) and runs[run_i][1] < s:
                run_i += 1
            module = runs[run_i][2] if (run_i < len(runs)
                                        and runs[run_i][0] <= s) else "?"
            m = _OPCODE.match(e.name)
            if m and m.group(1) in CONTAINERS:
                continue
            name_m = _INSTR.match(e.name)
            instr = name_m.group(1) if name_m else e.name
            smap = modules.get(module)
            scope = (smap.get(instr, "unscoped") if smap is not None
                     else f"other:{module}")
            dur = (min(t, hi) - max(s, lo)) * 1e-9
            per_scope[scope] += dur
            op_s[(scope, instr)] += dur
        merged = _union(_clip(ivs, lo, hi))
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        scope_s.append(dict(per_scope))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = 0.5 * (a + b)
                owners = [(he - hs, n) for hs, he, n in host if hs <= mid <= he]
                gap_s[min(owners)[1] if owners else "none"] += (b - a) * 1e-9
    all_gaps = sorted(gap_s.items(), key=lambda g: -g[1])
    n = max(1, len(planes))
    return {"window_s": (hi - lo) * 1e-9, "busy_s": sum(busy) / n,
            "scope_s": scope_s, "op_s": dict(op_s), "gaps": all_gaps,
            "chips": len(planes)}


def _anchor_lines(pd, anchor: str):
    """The host lines (threads) that carry the annotation ``anchor``."""
    return [line for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines if any(e.name == anchor for e in line.events)]


def host_window(pd, name: str):
    """(first start, last end) of the host annotation ``name``."""
    evs = [(e.start_ns, e.end_ns) for p in pd.planes
           if p.name.startswith("/host:") for line in p.lines
           for e in line.events if e.name == name]
    if not evs:
        raise ValueError(f"no host annotation {name!r} in the trace")
    return min(s for s, _ in evs), max(e for _, e in evs)


def breakdown(red: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a result line: the device ops that took most
    time (seconds summed over chips) and the longest idle gaps."""
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[f"{s}/{i}", v] for (s, i), v in ops],
            "idle_gaps": [[n, v] for n, v in red["gaps"][:top]]}
