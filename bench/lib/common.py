"""Shared harness pieces: seeds, the device gate, the peaks table, the
compile counter, and the number-beside-limit report of a correctness check.

Nothing here imports the program under test.
"""
from __future__ import annotations

import json
import math
import statistics
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


class NoDevice(SystemExit):
    """Raised (exit code 2) when JAX finds no TPU or too few chips."""


def seed_key(seed: int):
    """PRNG key for any whole seed, including ones wider than 32 bits: the
    low 31 bits seed the key and the rest is folded in."""
    import jax
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def device_gate(chips: int):
    """The cell's devices: exits non-zero, before any result, when JAX finds
    no TPU or fewer chips than the cell asks for. There is no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devs[0].platform} "
              f"({devs[0].device_kind}); there is no CPU fallback",
              file=sys.stderr)
        raise NoDevice(2)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips, JAX found {len(devs)}",
              file=sys.stderr)
        raise NoDevice(2)
    return devs[:chips]


def peaks_for(device_kind: str) -> dict:
    """Published per-chip peaks for ``device_kind``; a device that is not in
    the table is an error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"bench/peaks.json has no entry for device_kind "
                       f"{device_kind!r} (known: {sorted(table)})")
    return table[device_kind]


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads while ``armed``:
    the measured window must hold none."""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.armed and name in self._EVENTS:
            self.count += 1


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest of ``devices`` (0 where the
    backend keeps no statistics, as the CPU backend in tests)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def norm_gap(prog: dict, ref: dict, skip=()) -> tuple[float, str]:
    """Worst-leaf gap between two sets of per-leaf norms: |prog - ref| over
    the larger of the reference leaf's norm and the median leaf's norm.
    Leaves named in ``skip`` are left out. Returns (gap, leaf)."""
    keys = [k for k in ref if k not in skip]
    med = statistics.median(ref[k] for k in keys)
    worst, leaf = -1.0, ""
    for k in keys:
        g = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if not g <= worst:          # a NaN reading is the worst of all
            worst, leaf = g, k
    return worst, leaf


def still_leaves(ref_grad_norms: dict, frac: float = 1e-3) -> list:
    """Leaves whose reference gradient is nought to rounding: under a
    thousandth of the median leaf's. Their change is round-off alone."""
    med = statistics.median(ref_grad_norms.values())
    return sorted(k for k, v in ref_grad_norms.items() if v < frac * med)


def compare(prog, refr, limits) -> list:
    """[(name, value, limit, note)] of the numbers compared: the worst
    relative gap of the per-round losses; the relative gap of round 1's
    surrogate step, which carries the first gradient as the optimizer got
    it; and the worst-leaf gap of the norms of the change over the rounds
    compared."""
    gaps = [abs(a - b) / abs(b) for a, b in zip(prog["loss"], refr["loss"])]
    if len(prog["loss"]) != len(refr["loss"]):
        gaps.append(math.inf)
    loss_gap = max(gaps) if all(g <= math.inf for g in gaps) else math.inf
    grad_gap = abs(prog["surrogate"] - refr["surrogate"]) / refr["surrogate"]
    if not grad_gap <= math.inf:
        grad_gap = math.inf
    still = still_leaves(refr["grad"])
    delta_gap, delta_leaf = norm_gap(prog["delta"], refr["delta"], skip=still)
    n = len(refr["loss"])
    return [("loss_gap", loss_gap, limits["loss_gap"],
             f"worst of rounds 1-{n}"),
            ("grad_gap", grad_gap, limits["grad_gap"],
             "round 1's surrogate step, whole model"),
            ("delta_gap", delta_gap, limits["delta_gap"],
             f"change over rounds 1-{n}, worst leaf {delta_leaf}; "
             f"left out as still: {still}")]
