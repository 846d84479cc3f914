#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip at the cell's
own size. For each seed: the program's gaps to the plain reference (the
lower readings); for the control seeds, each control's gaps (the program
with its float32 products at a lower matmul precision, ``program:<p>``,
or the reference computed one step lower and put in the program's place,
``ref:<p>``) and each planted fault's (the upper readings); and whole runs
(``run.run_once``) with the program at a lower precision, which have to
come out not correct. One JSON line per reading; the benchmark's own runs
never run this.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 \\
        --control-seeds 1 2 3 --controls program:high ref:bf16 \\
        --faults half_batch altered --run-once-controls bfloat16
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]


def readings(prog, ref, limits, params0):
    """The compared numbers, and beside them ``diff``: the worst leaf of
    ||w - w_ref|| / ||w_ref - w0|| after the compared rounds."""
    import numpy as np
    from bench.lib.common import compare
    out = {n: v for n, v, _, _ in compare(prog, ref, limits)}
    out["diff"] = max(
        float(np.linalg.norm(prog["params"][k] - ref["params"][k])
              / np.linalg.norm(ref["params"][k] - np.asarray(params0[k])))
        for k in ref["params"])
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
    ap.add_argument("--controls", nargs="*", default=(),
                    help="program:<jax matmul precision> | ref:<high|bf16>")
    ap.add_argument("--faults", nargs="*", default=())
    ap.add_argument("--run-once-controls", nargs="*", default=(),
                    help="jax matmul precisions to run whole runs under")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    from bench.run import load_cell, log, run_once
    cell, config, traffic, e2e, _ = load_cell(args.workload)
    import importlib
    import jax
    from bench.lib import common
    from repro.launch import train
    devices = common.device_gate(cell["chips"])
    peaks = common.peaks_for(devices[0].device_kind)
    train.use_checkout_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    engine = importlib.import_module(f"bench.engines.{traffic['engine']}")
    lim = traffic["limits"]

    def emit(row):
        print(json.dumps(dict(workload=args.workload, **row)), flush=True)

    def program(seed, precision):
        jax.config.update("jax_default_matmul_precision", precision)
        eng = engine.Engine(config, traffic, seed, devices, log)
        eng.free()
        jax.config.update("jax_default_matmul_precision",
                          config["matmul_precision"])
        return eng

    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        t0 = time.perf_counter()
        eng = program(seed, config["matmul_precision"])
        t1 = time.perf_counter()
        ref = eng.reference()
        row = {"seed": seed, "ref_s": time.perf_counter() - t1,
               "program": readings(eng.prog, ref, lim, eng.params0)}
        if seed in args.control_seeds:
            for c in args.controls:
                kind, prec = c.split(":")
                other = (program(seed, prec).prog if kind == "program"
                         else eng.reference(prec))
                row[f"control:{c}"] = readings(other, ref, lim, eng.params0)
            for f in args.faults:
                row[f"fault:{f}"] = readings(eng.reference("f32", f), ref,
                                             lim, eng.params0)
        row["secs"] = time.perf_counter() - t0
        emit(row)
        del eng
    for prec in args.run_once_controls:
        for seed in args.control_seeds:
            result, _ = run_once(args.workload, dict(config, matmul_precision=prec),
                                 traffic, e2e, [], seed, args.seconds, 0,
                                 devices, peaks)
            emit({"seed": seed, "run_once_control": prec,
                  "correct": result["correct"], "checks": result["checks"]})


if __name__ == "__main__":
    main()
