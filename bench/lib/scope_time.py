"""Device seconds of the ops under one of the program's own scopes, for
scopes that ``bench/run.py``'s ``SCOPES`` does not name: an engine puts the
{instruction: innermost program scope} map of its window program in
``counts()["scope_map"]`` (``trace.scopes_from_hlo`` over the program's
``obs.trace.PHASES``), and the traced window's ops (``op_s``, summed over
chips, keyed by the harness's scope and the instruction) are summed by it.
Ops of other modules (``other:<module>``) are left out.
"""
from __future__ import annotations


def seconds(ctx: dict, scope: str) -> float:
    smap = ctx["counts"].get("scope_map") or {}
    return sum(v for (outer, instr), v in ctx["trace"]["op_s"].items()
               if not outer.startswith("other:") and smap.get(instr) == scope)


def ms_per_round(ctx: dict, scope: str):
    """Per-chip device ms per traced round under ``scope``, or None where
    the window ran no op under it."""
    secs = seconds(ctx, scope)
    if secs <= 0 or not ctx["rounds"]:
        return None
    return 1e3 * secs / ctx["chips"] / ctx["rounds"]
