"""Roofline terms from compiled dry-run artifacts (no real hardware).

    compute term    = HLO_FLOPs / peak_FLOP/s          (per chip)
    memory term     = HLO_bytes / HBM_bw               (per chip)
    collective term = collective_bytes / link_bw       (per chip)

Sources: ``compiled.cost_analysis()`` for FLOPs/bytes (the post-SPMD module is
per-device, so these are per-chip numbers). collective_bytes is parsed from
the HLO text: the summed output sizes of all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute ops (a wire-bytes upper bound
of ~(n-1)/n tightness; consistent across the whole table so deltas are
meaningful).

Hardware constants (TPU v5e target): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict

import numpy as np


@dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12      # bf16 per chip
    hbm_bw: float = 819e9           # bytes/s per chip
    link_bw: float = 50e9           # bytes/s per ICI link


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16, "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g. "bf16[16,2048,128]{2,1,0}" or "f32[]"
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(?[^=]*?\)?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.M)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        nbytes = _DTYPE_BYTES.get(dt)
        if nbytes is None:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * nbytes
    return total


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Sum of output bytes per collective kind (per device). ``-start`` ops are
    counted, matching ``-done`` pairs are not double counted."""
    out = {k: 0 for k in _COLLECTIVES}
    seen_done = set()
    for m in _OP_RE.finditer(hlo_text):
        shapes, kind = m.group(1), m.group(2)
        line = m.group(0)
        if "-done(" in line:
            continue  # bytes counted at the -start op
        out[kind] += _shape_bytes(shapes)
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


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
    denom = max(terms[dom], 1e-30)
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


def model_flops(cfg, num_tokens: int, param_count: int,
                active_param_count: int | None = None) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE)."""
    n = active_param_count if active_param_count is not None else param_count
    return 6.0 * n * num_tokens


def count_params(tree) -> int:
    import jax
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(tree)))


def active_params(cfg, tree) -> int:
    """Active-per-token parameter count: MoE expert tensors scaled by k/E."""
    import jax
    if not getattr(cfg, "n_experts", 0):
        return count_params(tree)
    frac = cfg.experts_per_token / cfg.n_experts
    total = 0
    for path, leaf in jax.tree.flatten_with_path(tree)[0]:
        pstr = "/".join(str(p) for p in path)
        n = int(np.prod(leaf.shape))
        if "moe" in pstr and any(w in pstr for w in ("wi", "wg", "wo")) \
                and "dense" not in pstr:
            total += int(n * frac)
        else:
            total += n
    return total
