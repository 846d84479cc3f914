#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of BENCHMARK.json's ``workloads``: its configuration
is ``bench/configs/<config>.json``, its traffic ``bench/traffic/<traffic>
.json``, whose ``engine`` names ``bench/engines/<engine>.py``; each
per-layer metric is read by ``bench/metrics/<metric>.py``. Nothing here
lists them.

A run: set-up (weights and data from the seed on the device, the
window's first dispatch, which ``correct`` compares and which warms the
window's program), then
``--seconds`` of dispatches with the profiler off (``--trace 0``), or a few
traced dispatches reduced to the per-layer metrics (``--trace 1``); then
the peak device memory, then the plain reference, and last the result line.
It exits 2, printing no result, when JAX finds no TPU or too few chips.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TRACE_SECONDS, TRACE_MIN_DISPATCHES = 2.0, 2


def log(msg):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_cell(name: str):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        sys.exit(f"bench: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json").read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    per_layer = [m for m in spec["per_layer"] if name in m.get("workloads", [name])]
    return cell, config, traffic, e2e, per_layer


def read_metric(name: str, ctx: dict):
    """The value of per-layer metric ``name`` from its reader file, or None
    where the reader finds nothing to read."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def nonfinite(losses) -> int:
    """Rounds whose loss is not finite, over the window's dispatches (read
    once the window has closed)."""
    import numpy as np
    return sum(int(np.sum(~np.isfinite(np.asarray(x)))) for x in losses)


def timed_window(eng, seconds, counter):
    counter.armed = True
    t0 = time.perf_counter()
    work = attempted = 0
    losses = []
    while True:
        w = eng.dispatch()
        work += w
        attempted += eng.rounds_of(w)
        losses.append(eng.losses())
        if time.perf_counter() - t0 >= seconds:
            break
    window = time.perf_counter() - t0
    counter.armed = False
    return work, attempted, nonfinite(losses), window


def traced_window(eng):
    import jax
    from bench.lib import trace as tr

    modules = dict(tr.scopes_from_hlo(t, SCOPES) for t in eng.module_texts())
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    work = attempted = n = 0
    losses = []
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        t0 = time.perf_counter()
        while n < TRACE_MIN_DISPATCHES or time.perf_counter() - t0 < TRACE_SECONDS:
            with jax.profiler.TraceAnnotation("bench/dispatch"):
                w = eng.dispatch()
            work += w
            attempted += eng.rounds_of(w)
            losses.append(eng.losses())
            n += 1
        jax.profiler.stop_trace()
        path = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")[0]
        pd = jax.profiler.ProfileData.from_file(path)
        red = tr.reduce_trace(pd, tr.host_window(pd, "bench/dispatch"),
                              modules, "bench/dispatch")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return work, attempted, nonfinite(losses), red


SCOPES = ("round", "client-compute", "codec-encode", "collective", "aggregate",
          "head-compute", "batch-select", "cohort-select", "surrogate-solve",
          "dp-privatize")


def run_once(name, config, traffic, e2e, per_layer, seed, seconds, trace,
             devices, peaks):
    """One run of a cell on ``devices`` after the device gate: set-up, the
    window, the peak memory, the reference. Returns (result, checks)."""
    import jax
    from bench.lib import common
    from bench.lib.trace import breakdown

    # the precision the configuration states for its float32 products; on
    # the TPU the default would run them as one bfloat16 pass
    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])
    counter = common.CompileCounter()
    engine = importlib.import_module(f"bench.engines.{traffic['engine']}")
    eng = engine.Engine(config, traffic, seed, devices, log)
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {}}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    checks = []
    if trace:
        work, attempted, failed, red = traced_window(eng)
        ctx = {"trace": red, "counts": eng.counts(), "peaks": peaks,
               "rounds": eng.rounds_of(work), "chips": len(devices)}
        for m in per_layer:
            v = read_metric(m["name"], ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = breakdown(red)
    else:
        setup_s = time.perf_counter() - T_START
        work, attempted, failed, window = timed_window(eng, seconds, counter)
        readings = {"setup_s": setup_s, f"{eng.unit}_per_s": work / window}
        checks.append(("compiles_in_window", counter.count, 0, ""))
        log(f"window {window} s, {attempted} rounds")
    peak = common.memory_peak_bytes(devices)
    device["memory_peak_bytes"] = peak
    if not trace:
        readings["peak_hbm_gb"] = peak / 1e9
        for m in e2e:
            result["metrics"][m["name"]] = {"value": readings[m["name"]],
                                            "unit": m["unit"]}
    eng.free()
    t_ref = time.perf_counter()
    checks = eng.check() + checks
    log(f"reference {time.perf_counter() - t_ref} s")
    checks.append(("failed_rounds", failed, 0, "non-finite losses"))
    ok = all(lim is not None and v <= lim for _, v, lim, _ in checks)
    result.update(correct=ok, attempted=attempted, failed=failed,
                  device=device)
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim, _ in checks}
    return result, checks


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    cell, config, traffic, e2e, per_layer = load_cell(args.workload)

    import jax
    from bench.lib import common
    from repro.launch import train

    devices = common.device_gate(cell["chips"])
    peaks = common.peaks_for(devices[0].device_kind)
    train.use_checkout_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result, checks = run_once(args.workload, config, traffic, e2e, per_layer,
                              args.seed, args.seconds, args.trace, devices,
                              peaks)
    for n, v, lim, note in checks:
        print(f"check {n} = {v!r} limit {lim!r} ({note})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
