"""Roofline terms of a compiled program's cost (no real hardware), and the
per-dispatch cost summary of a jitted function.

    compute term    = HLO_FLOPs / peak_FLOP/s          (per chip)
    memory term     = HLO_bytes / HBM_bw               (per chip)
    collective term = collective_bytes / link_bw       (per chip)

Hardware constants (TPU v5e target): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12      # bf16 per chip
    hbm_bw: float = 819e9           # bytes/s per chip
    link_bw: float = 50e9           # bytes/s per ICI link


def roofline_terms(cost: dict, coll_bytes: int, hw: HW = HW()) -> dict:
    flops = float(cost.get("flops", 0) or 0)
    # cost_analysis exposes bytes accessed as "bytes accessed"
    bts = float(cost.get("bytes accessed", 0) or 0)
    terms = {
        "flops": flops,
        "bytes": bts,
        "collective_bytes": float(coll_bytes),
        "compute_s": flops / hw.peak_flops,
        "memory_s": bts / hw.hbm_bw,
        "collective_s": float(coll_bytes) / hw.link_bw,
    }
    dom = max(("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])
    terms["bottleneck"] = dom.replace("_s", "")
    terms["bound_s"] = terms[dom]
    return terms


def jit_cost_summary(fn, *args) -> dict:
    """Compile ``fn(*args)`` and summarize its per-dispatch HLO cost.

    Returns ``{"xla": {...}, "flops": ..., "bytes": ..., "collectives": ...}``
    — the XLA ``cost_analysis()`` dict alongside this package's own
    HLO-text analysis. A program that does not lower or compile raises: the
    run it describes would fail the same way."""
    import jax

    from repro.roofline import hlo_cost

    compiled = jax.jit(fn).lower(*args).compile()
    parsed = hlo_cost.analyze(compiled.as_text())
    return {"xla": hlo_cost.xla_cost_analysis(compiled),
            **{k: parsed[k] for k in ("flops", "bytes", "collectives")}}
