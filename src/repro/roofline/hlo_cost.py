"""While-aware HLO cost model.

XLA's built-in ``compiled.cost_analysis()`` counts a while-loop body ONCE —
with scan-over-layers models that undercounts FLOPs/bytes/collectives by ~L×.
This parser walks the post-optimization HLO text, extracts per-computation
costs, and multiplies by loop trip counts (available in the while op's
``backend_config={"known_trip_count":{"n":...}}``), propagating multipliers
through nested scans (e.g. xLSTM's time-scan inside the layer-scan).

Counted:
  flops             2·prod(out)·prod(contracted) per dot (incl. inside fusions)
  bytes             operand+output bytes of top-level instructions (fusion
                    internals excluded — they live in registers/VMEM)
  collective bytes  output bytes per collective kind

This is the cost source for §Roofline; tests validate it against XLA's own
cost_analysis on loop-free (unrolled) modules.
"""
from __future__ import annotations

import json
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COMP_HDR = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")
# type is either a tuple "(...)" (no nested parens; may contain /*index=N*/
# comments) or a plain array type "f32[1,2]{1,0}"
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*((?:\([^()]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[0-9,:TSD()]*\})?))\s+"
    r"([\w\-]+)\((.*)$")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_BODY = re.compile(r"body=%([\w.\-]+)")
_COND = re.compile(r"condition=%([\w.\-]+)")
_TO_APPLY = re.compile(r"to_apply=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_LHS_CDIMS = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
# ops whose "bytes accessed" we do not charge (metadata/aliasing/no real traffic)
_SKIP_BYTES = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
               "after-all", "add-dependency", "iota", "partition-id", "replica-id"}


def _shape_dims(shape_str: str):
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return None, ()
    dt, dims = m.group(1), m.group(2)
    return dt, (tuple(int(d) for d in dims.split(",")) if dims else ())


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        nb = _DTYPE_BYTES.get(dt)
        if nb is None:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * nb
    return total


@dataclass
class Instr:
    name: str
    shape: str
    op: str
    rest: str          # operand list + attributes (tail of line)


@dataclass
class Computation:
    name: str
    is_entry: bool
    instrs: List[Instr] = field(default_factory=list)
    defs: Dict[str, str] = field(default_factory=dict)   # name -> shape str


def parse_computations(hlo: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in hlo.splitlines():
        m = _COMP_HDR.match(line)
        if m:
            cur = Computation(name=m.group(2), is_entry=bool(m.group(1)))
            comps[cur.name] = cur
            continue
        if cur is None:
            continue
        if line.startswith("}"):
            cur = None
            continue
        mi = _INSTR.match(line)
        if mi:
            ins = Instr(name=mi.group(1), shape=mi.group(2), op=mi.group(3),
                        rest=mi.group(4))
            cur.instrs.append(ins)
            cur.defs[ins.name] = ins.shape
    return comps


def _dot_flops(ins: Instr, defs: Dict[str, str]) -> float:
    out_bytes_dims = _shape_dims(ins.shape)[1]
    out_elems = 1
    for d in out_bytes_dims:
        out_elems *= d
    cd = _LHS_CDIMS.search(ins.rest)
    contracted = 1
    if cd:
        idxs = [int(x) for x in cd.group(1).split(",") if x]
        ops = _OPERAND.findall(ins.rest)
        if ops:
            lhs_shape = defs.get(ops[0], "")
            dims = _shape_dims(lhs_shape)[1]
            for i in idxs:
                if i < len(dims):
                    contracted *= dims[i]
    return 2.0 * out_elems * contracted


def _conv_flops(ins: Instr, defs: Dict[str, str]) -> float:
    # flops ~= 2 * prod(out) * kernel_elems_per_output; approximate via rhs size
    out_dims = _shape_dims(ins.shape)[1]
    out_elems = 1
    for d in out_dims:
        out_elems *= d
    ops = _OPERAND.findall(ins.rest)
    k_elems = 1
    if len(ops) >= 2:
        kdims = _shape_dims(defs.get(ops[1], ""))[1]
        for d in kdims:
            k_elems *= d
        odims = _shape_dims(ins.shape)[1]
        if odims:
            k_elems = max(1, k_elems // max(1, odims[-1]))  # per-output-channel
    return 2.0 * out_elems * k_elems


_SLICE_READS_OUTPUT = {"dynamic-slice", "slice", "gather"}


def _operands(ins: Instr):
    paren = ins.rest.split(")", 1)[0]
    return _OPERAND.findall(paren)


def _fusion_traffic(comp: Computation) -> float:
    """HBM traffic of a fused computation: root output + per-parameter read
    bytes. Slice-aware (a param only consumed through (dynamic-)slices is
    charged the sliced bytes) and DUS-aware (a dynamic-update-slice root
    aliases its base buffer in place: charge the update region, not the whole
    buffer — scan checkpoint stacks otherwise overcount by the trip count)."""
    if not comp.instrs:
        return 0.0
    root = comp.instrs[-1]
    params = {i.name: i.shape for i in comp.instrs if i.op == "parameter"}
    defs = comp.defs
    dus_bases = set()
    out = _shape_bytes(root.shape)
    if root.op == "dynamic-update-slice":
        ops = _operands(root)
        if ops:
            dus_bases.add(ops[0])
            upd = _shape_bytes(defs.get(ops[1], "")) if len(ops) > 1 else out
            out = upd                                 # in-place: write region only
    read = {p: 0.0 for p in params}
    full = {p: False for p in params}
    for ins in comp.instrs:
        if ins.op == "parameter":
            continue
        for j, opn in enumerate(_operands(ins)):
            if opn not in params:
                continue
            if ins.op in _SLICE_READS_OUTPUT:
                read[opn] += _shape_bytes(ins.shape)
            elif ins.op == "dynamic-update-slice" and j == 0:
                pass                                  # aliased base buffer
            else:
                full[opn] = True
    total = out
    for p, shp in params.items():
        total += _shape_bytes(shp) if full[p] else min(read[p], _shape_bytes(shp))
    return total


def _instr_bytes(ins: Instr, defs: Dict[str, str], comps, fusion_traffic) -> float:
    if ins.op in _SKIP_BYTES or ins.op.endswith("-done"):
        return 0.0
    if ins.op == "fusion":
        called = _CALLS.findall(ins.rest)
        if called and called[0] in fusion_traffic:
            return fusion_traffic[called[0]]
    out = _shape_bytes(ins.shape)
    if ins.op in _SLICE_READS_OUTPUT:
        return 2.0 * out
    if ins.op == "dynamic-update-slice":
        ops = _operands(ins)
        upd = _shape_bytes(defs.get(ops[1], "")) if len(ops) > 1 else out
        return 2.0 * upd               # read update + write update (in-place base)
    if ins.op == "scatter":
        ops = _operands(ins)
        upd = _shape_bytes(defs.get(ops[-1], "")) if ops else out
        return 2.0 * upd + out
    b = out
    for opn in _operands(ins):
        b += _shape_bytes(defs.get(opn, ""))
    return b


def xla_cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a plain dict (keys such as "flops"
    and "bytes accessed" vary by backend — callers .get() them)."""
    return dict(compiled.cost_analysis() or {})


def analyze(hlo: str) -> dict:
    comps = parse_computations(hlo)
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None:
        raise ValueError("no ENTRY computation found")

    # fusion-internal traffic (slice-aware)
    fusion_traffic = {c.name: _fusion_traffic(c) for c in comps.values()
                      if not c.is_entry}

    # ---- local (single-execution) cost of each computation ----
    local = {}
    for c in comps.values():
        flops = 0.0
        bts = 0.0
        coll = defaultdict(float)
        for ins in c.instrs:
            if ins.op == "dot":
                flops += _dot_flops(ins, c.defs)
            elif ins.op == "convolution":
                flops += _conv_flops(ins, c.defs)
            base = ins.op.replace("-start", "").replace("-done", "")
            if base in _COLLECTIVES and not ins.op.endswith("-done"):
                coll[base] += _shape_bytes(ins.shape)
            bts += _instr_bytes(ins, c.defs, comps, fusion_traffic)
        local[c.name] = {"flops": flops, "bytes": bts, "coll": dict(coll)}

    # ---- call-graph multipliers ----
    mult = defaultdict(float)
    mult[entry.name] = 1.0
    work = [entry.name]
    seen_edges = set()
    fusion_like = set()
    while work:
        cname = work.pop()
        m = mult[cname]
        c = comps.get(cname)
        if c is None:
            continue
        for ins in c.instrs:
            children = []
            trip = 1.0
            if ins.op == "while":
                tb = _TRIP.search(ins.rest)
                trip = float(tb.group(1)) if tb else 1.0
                children += _BODY.findall(ins.rest) + _COND.findall(ins.rest)
            elif ins.op == "fusion" or ins.op in ("call", "custom-call", "map"):
                ch = _CALLS.findall(ins.rest) + _TO_APPLY.findall(ins.rest)
                children += ch
                fusion_like.update(ch)
            elif ins.op == "conditional":
                br = _BRANCHES.search(ins.rest)
                if br:
                    children += [x.strip().lstrip("%") for x in br.group(1).split(",")]
                children += _TO_APPLY.findall(ins.rest)
                fusion_like.update(children)
            elif ins.op in ("reduce", "reduce-window", "scatter", "sort",
                            "select-and-scatter", "all-reduce", "reduce-scatter"):
                # tiny scalar to_apply computations — ignore
                continue
            for ch in children:
                edge = (cname, ch, ins.name)
                if edge in seen_edges:
                    continue
                seen_edges.add(edge)
                mult[ch] += m * trip
                work.append(ch)

    # ---- totals ----
    # bytes: only "top-level" computations (entry, while bodies/conds,
    # conditional branches) — i.e. everything except fusion-internal comps.
    tot_flops = 0.0
    tot_bytes = 0.0
    tot_coll = defaultdict(float)
    for cname, m in mult.items():
        if m == 0.0 or cname not in local:
            continue
        lc = local[cname]
        tot_flops += m * lc["flops"]
        if cname not in fusion_like:
            tot_bytes += m * lc["bytes"]
        for k, v in lc["coll"].items():
            tot_coll[k] += m * v
    tot_coll["total"] = sum(tot_coll[k] for k in _COLLECTIVES if k in tot_coll)
    return {"flops": tot_flops, "bytes": tot_bytes,
            "collectives": dict(tot_coll), "multipliers": dict(mult)}
