import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination against the production mesh with ShapeDtypeStruct stand-ins (no
allocation), printing memory_analysis / cost_analysis and the roofline terms.

MUST keep the two lines above as the very first statements — jax locks the
device count on first init, and smoke tests/benches must still see 1 device
(this env var is process-local to the dry-run).

CLI:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json out.json]
"""
import argparse       # noqa: E402
import json           # noqa: E402
import sys            # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402

import jax            # noqa: E402
import jax.numpy as jnp                      # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import FLConfig, get_config           # noqa: E402
from repro.configs.registry import ARCHS, ASSIGNED       # noqa: E402
from repro.configs.shapes import SHAPES, supports_shape  # noqa: E402
from repro.configs import shapes as shapes_lib           # noqa: E402
from repro.core import optimizer                         # noqa: E402
from repro.launch import mesh as mesh_lib                # noqa: E402
from repro.launch import serve as serve_lib              # noqa: E402
from repro.launch import train as train_lib              # noqa: E402
from repro.models import get_model                       # noqa: E402
from repro.roofline import (HW, collective_bytes_from_hlo,  # noqa: E402
                            model_flops, roofline_terms)
from repro.roofline.analysis import active_params, count_params  # noqa: E402


def _state_shapes(model, cfg, constrained: bool):
    """SSCA train state as ShapeDtypeStructs (init evaluated shape-only)."""
    def build():
        params = model.init(jax.random.PRNGKey(0), cfg)
        return (optimizer.ssca_constrained_init(params) if constrained
                else optimizer.ssca_init(params))
    return jax.eval_shape(build)


def lower_one(arch: str, shape_name: str, *, multi_pod: bool = False,
              constrained: bool = False, fl: FLConfig = None, verbose: bool = True,
              overrides: dict = None):
    """Lower + compile one (arch, shape, mesh). Returns result dict.
    overrides: ModelConfig field overrides (the §Perf hillclimb knobs)."""
    import dataclasses
    cfg = get_config(arch)
    if overrides:
        typed = {}
        for k, v in overrides.items():
            fld = {f.name: f.type for f in dataclasses.fields(cfg)}[k]
            if isinstance(v, str):
                if v.lower() in ("true", "false"):
                    v = v.lower() == "true"
                elif v.lstrip("-").isdigit():
                    v = int(v)
            typed[k] = v
        cfg = dataclasses.replace(cfg, **typed)
    shape = SHAPES[shape_name]
    ok, why = supports_shape(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped", "why": why}

    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    model = get_model(cfg)
    fl = fl or FLConfig(tau=0.2, l2_lambda=1e-5)
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            batch = shapes_lib.train_specs(cfg, shape)
            state = _state_shapes(model, cfg, constrained)
            step = (train_lib.make_constrained_train_step if constrained
                    else train_lib.make_train_step)(model, cfg, fl)
            sspec = mesh_lib.named_fitted(
                mesh, train_lib.state_specs(model, cfg, constrained), state)
            bspec = mesh_lib.named_fitted(
                mesh, train_lib.batch_specs(batch, mesh), batch)
            lowered = jax.jit(step, in_shardings=(sspec, bspec),
                              out_shardings=(sspec, None),
                              donate_argnums=(0,)).lower(state, batch)
            num_tokens = shape.global_batch * shape.seq_len
        elif shape.kind == "prefill":
            batch = shapes_lib.prefill_specs(cfg, shape)
            params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), cfg))
            pspec = mesh_lib.named_fitted(
                mesh, model.param_specs(cfg, mode="serve"), params)
            bspec = mesh_lib.named_fitted(
                mesh, train_lib.batch_specs(batch, mesh), batch)
            lowered = jax.jit(
                lambda p, b: model.prefill(p, b, cfg),
                in_shardings=(pspec, bspec)).lower(params, batch)
            num_tokens = shape.global_batch * shape.seq_len
        else:  # decode
            token, pos, cache = shapes_lib.decode_specs(cfg, shape)
            params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), cfg))
            step = serve_lib.make_decode_step(model, cfg)
            pspec = mesh_lib.named_fitted(
                mesh, model.param_specs(cfg, mode="serve"), params)
            cspec = mesh_lib.named_fitted(
                mesh, mesh_lib.adapt_for_mesh(model.cache_specs(cfg), mesh), cache)
            axes = mesh_lib.data_axes(mesh)
            tspec = mesh_lib.named_fitted(mesh, P(axes), token)
            rspec = jax.sharding.NamedSharding(mesh, P())
            lowered = jax.jit(step, in_shardings=(pspec, cspec, tspec, rspec),
                              out_shardings=(tspec, cspec),
                              donate_argnums=(1,)).lower(params, cache, token, pos)
            num_tokens = shape.global_batch      # one new token per sequence

        compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        from repro.roofline import hlo_cost
        # raw XLA numbers (while bodies x1); list/dict + key drift normalized
        cost_xla = hlo_cost.xla_cost_analysis(compiled)
        parsed = hlo_cost.analyze(hlo)           # while-aware (see roofline/hlo_cost.py)
        coll = parsed["collectives"]
        coll.setdefault("total", 0.0)
        terms = roofline_terms(
            {"flops": parsed["flops"], "bytes accessed": parsed["bytes"]},
            coll["total"])

        params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), cfg))
        n_params = count_params(params_shape)
        n_active = active_params(cfg, params_shape)
        chips = mesh.devices.size
        mflops = model_flops(cfg, num_tokens, n_params, n_active)
        if shape.kind == "train":
            mflops *= 1.0        # 6ND already includes fwd+bwd
        else:
            mflops /= 3.0        # forward only: 2ND
        useful = mflops / chips / max(terms["flops"], 1e-30)

        result = {
            "arch": arch, "shape": shape_name, "kind": shape.kind,
            "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
            "status": "ok", "compile_s": round(t_compile, 1),
            "params": n_params, "active_params": n_active,
            "model_flops_per_chip": mflops / chips,
            "useful_flop_ratio": useful,
            "memory": {
                "bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            },
            "collectives": coll,
            "xla_raw": {"flops": cost_xla.get("flops"),
                        "bytes": cost_xla.get("bytes accessed")},
            **{k: terms[k] for k in ("flops", "bytes", "collective_bytes",
                                     "compute_s", "memory_s", "collective_s",
                                     "bottleneck")},
        }
        if verbose:
            print(f"[{result['mesh']}] {arch} x {shape_name}: OK "
                  f"compile={t_compile:.0f}s bottleneck={result['bottleneck']} "
                  f"compute={terms['compute_s']*1e3:.2f}ms "
                  f"memory={terms['memory_s']*1e3:.2f}ms "
                  f"collective={terms['collective_s']*1e3:.2f}ms "
                  f"useful={useful:.2f}", flush=True)
        return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--constrained", action="store_true")
    ap.add_argument("--json", default=None)
    ap.add_argument("--set", action="append", default=[],
                    help="ModelConfig override, e.g. --set attention_impl=chunked")
    args = ap.parse_args()
    overrides = dict(s.split("=", 1) for s in args.set)

    combos = []
    archs = ASSIGNED if args.all else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for mp in meshes:
        for a in archs:
            for s in shapes:
                combos.append((a, s, mp))

    results, failures = [], []
    for a, s, mp in combos:
        try:
            r = lower_one(a, s, multi_pod=mp, constrained=args.constrained,
                          overrides=overrides)
        except Exception as e:
            traceback.print_exc()
            r = {"arch": a, "shape": s, "mesh": "2x16x16" if mp else "16x16",
                 "status": "error", "error": f"{type(e).__name__}: {e}"}
            failures.append(r)
            print(f"[{'2x16x16' if mp else '16x16'}] {a} x {s}: FAIL {e}",
                  flush=True)
        results.append(r)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    print(f"\n== dry-run: {n_ok} ok, {n_skip} skipped, {len(failures)} failed "
          f"of {len(results)}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1, default=str)
        print("wrote", args.json)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
