#!/usr/bin/env python3
"""Drive the repo's main paths once on a TPU, through the library's own
training loops, and check what comes out.

    python chip_smoke.py             # one chip: LM job, cohort FL, feature FL
    python chip_smoke.py --chips 4   # four chips: sharded engines vs local only

One chip, in order:

1. device gate: JAX must find a TPU, else exit non-zero before any phase
   (there is no CPU fallback);
2. LM job: ``train_loop`` (the cross-silo LM job on the round engine) on
   qwen2.5-3b at its published widths, bf16 and remat kept, depth cut to
   ``LM_LAYERS``: ``LM_S`` of ``LM_SILOS`` silos a round, ``LM_BATCH``
   rows of ``LM_SEQ`` tokens each, ``LM_ROUNDS`` rounds in one dispatch.
   Every round's loss estimate is finite, and round 1's matches a float32
   rebuild of it outside the engine within ``LM_RTOL`` relative;
3. cohort engine (sample-based FL, Algorithm 1): ``cohort_train_loop`` at
   a population of 1e6 with cohorts of 256 and the int8 codec on its
   compiled Pallas quantizer (the scanned step must hold a
   ``tpu_custom_call``). Its trajectory equals the jnp codec's (both
   consume the same random bits) within ``ATOL``, and its JSONL stream
   under ``run/chip_smoke/`` holds one row per round;
4. feature engine (feature-based FL, constrained Algorithm 4):
   ``feature_train_loop``; loss, ``cons_viol`` and ``nu`` finite.

``--chips 4`` runs only the multi-chip path and the run it is compared
with: the sharded cohort engine (256 clients over 4 chips) and the sharded
feature engine (4 clients on the "model" axis), each equal to its local
run within ``ATOL``, with every carry leaf's sharding printed.

Everything runs in this one process. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
a failed check raises, so that line is only printed when all phases pass.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import FLConfig, get_config  # noqa: E402
from repro.core import fed, rounds  # noqa: E402
from repro.data.synthetic import VirtualTokenData  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.models import transformer  # noqa: E402

LM_ARCH = "qwen2.5-3b"
# the most layers whose K-round program leaves >= 2 GB of the 16 GB chip
# free by memory_analysis() (tests/test_tpu_compile.py holds it there)
LM_LAYERS = 6
LM_SILOS, LM_S, LM_BATCH, LM_SEQ, LM_ROUNDS = 8, 2, 2, 512, 5
# bf16 training forward vs the float32 reference forward, relative
LM_RTOL = 2e-2
SEED = 0

COHORT_CLIENTS, COHORT_S, COHORT_ROUNDS = 1_000_000, 256, 20
FEATURE_CLIENTS, FEATURE_ROUNDS = 4, 20
# engine-vs-engine trajectories: same math, f32 reduction order only
ATOL = 1e-5
# Algorithm 4's nu and slack come from the Lemma-1 closed form at penalty
# c = 1e4, which amplifies f32 reassociation across collectives: they are
# held relatively, as tests/test_feature_topology.py holds them
CLOSED_FORM, CLOSED_FORM_RTOL = ("nu", "slack", "round_nu", "round_slack"), 1e-3

OUT = ROOT / "run" / "chip_smoke"


class SmokeFailure(AssertionError):
    """A phase produced a wrong or non-finite result."""


def check(ok: bool, msg: str):
    if not ok:
        raise SmokeFailure(msg)


def lm_config():
    """qwen2.5-3b at its published widths, depth cut to LM_LAYERS."""
    return dataclasses.replace(get_config(LM_ARCH), n_layers=LM_LAYERS)


def lm_reference_loss(cfg, silos: int, participation: int, batch: int,
                      seq: int, num_rounds: int, seed: int = SEED) -> float:
    """Round 1's loss estimate of ``train_loop`` rebuilt outside the engine,
    in float32: the population, initial params and run key as
    ``train_loop`` derives them from ``seed`` (``fold_in(key, 0xDA7A)``,
    ``fold_in(key, 1)``, ``fold_in(key, 2)``), round 1's key as
    ``rounds.run_rounds`` splits it for one dispatch of ``num_rounds``
    rounds (as ``bench/engines/lm_silo.round_keys`` does), then the
    cohort, each silo's rows and the ``fed.cohort_weights`` weights by the
    engine's key derivation, and sum_i w_i sum_b loss(row) one silo at a
    time: params cast up to f32, no remat, highest matmul precision."""
    key = jax.random.PRNGKey(seed)
    data = VirtualTokenData(jax.random.fold_in(key, 0xDA7A), silos, seq,
                            cfg.vocab_size)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          transformer.init(jax.random.fold_in(key, 1), cfg))
    sub = jax.random.split(jax.random.fold_in(key, 2))[1]
    k1 = rounds.make_inputs(FLConfig(), 1, num_rounds, sub).key[0]
    ids = fed.cohort_sample(jax.random.fold_in(k1, 0x5CA), silos,
                            participation)
    counts = data.counts_for(ids)
    weights = fed.cohort_weights(counts, batch, silos, data.total)
    f32 = dataclasses.replace(cfg, dtype="float32", remat=False)
    loss = jax.jit(lambda p, x, y: transformer.per_sequence_loss(p, x, y,
                                                                 f32)[0])
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for i, n_i, w_i in zip(ids, counts, weights):
            rows = jax.random.randint(jax.random.fold_in(k1, i), (batch,), 0,
                                      n_i)[:min(batch, int(n_i))]
            x, y = data.batch_rows(i[None], rows[None])
            total += float(w_i) * float(jnp.sum(loss(params, x[0], y[0])))
    return total


def device_gate(count: int):
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {d.platform} "
                 f"({d.device_kind}); there is no CPU fallback")
    if len(devs) < count:
        sys.exit(f"chip_smoke: --chips {count} needs {count} devices, "
                 f"JAX found {len(devs)}")
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} "
          f"bytes_limit={d.memory_stats().get('bytes_limit')}", flush=True)
    return d


def lm_phase(dev):
    cfg = lm_config()
    print(f"== LM job: {cfg.name} ({cfg.source}) layers={cfg.n_layers} "
          f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype} "
          f"remat={cfg.remat} silos={LM_SILOS} cohort={LM_S} "
          f"batch={LM_BATCH} seq={LM_SEQ} rounds={LM_ROUNDS}", flush=True)
    t0 = time.perf_counter()
    res = train.train_loop(cfg, clients=LM_SILOS, participation=LM_S,
                           rounds=LM_ROUNDS, batch=LM_BATCH, seq=LM_SEQ,
                           log_every=1, seed=SEED)
    losses = [float(v) for v in res.history["round_loss_est"]]
    run_s = time.perf_counter() - t0
    del res
    check(len(losses) == LM_ROUNDS, f"expected {LM_ROUNDS} losses: {losses}")
    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"lm losses={losses}", flush=True)
    print(f"lm run_s={run_s} peak_bytes_in_use={peak}", flush=True)
    ref = lm_reference_loss(cfg, LM_SILOS, LM_S, LM_BATCH, LM_SEQ, LM_ROUNDS)
    rel = abs(losses[0] - ref) / abs(ref)
    print(f"lm round1 loss={losses[0]} f32_reference={ref} rel_diff={rel}",
          flush=True)
    check(rel <= LM_RTOL, f"round-1 loss {losses[0]} vs f32 reference {ref}: "
          f"relative diff {rel} > {LM_RTOL}")


def _max_diff(a, b) -> float:
    """Largest |a - b| over two matching pytrees of arrays."""
    return max(float(jnp.max(jnp.abs(jnp.asarray(x, jnp.float32)
                                     - jnp.asarray(y, jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def compare_runs(name: str, a, b):
    """Two RunResults of the same seed: params and every history series
    equal within ATOL (CLOSED_FORM series within CLOSED_FORM_RTOL of the
    second run), except ``round_axis_bytes`` (the bytes a topology moves
    between devices, 0 on one device by definition)."""
    check(sorted(a.history) == sorted(b.history),
          f"{name}: history keys differ {sorted(a.history)} vs "
          f"{sorted(b.history)}")
    d_params = _max_diff(a.params, b.params)
    d_hist = {k: _max_diff(a.history[k], b.history[k]) for k in a.history
              if k != "round_axis_bytes"}
    print(f"{name}: max|params diff|={d_params} max|history diff| by "
          f"series={d_hist}", flush=True)
    def limit(k):
        if k not in CLOSED_FORM:
            return ATOL
        scale = float(jnp.max(jnp.abs(jnp.asarray(b.history[k], jnp.float32))))
        return ATOL + CLOSED_FORM_RTOL * scale

    bad = [k for k, d in d_hist.items() if d > limit(k)]
    check(d_params <= ATOL and not bad,
          f"{name}: params diff {d_params} or series {bad} beyond atol "
          f"{ATOL}")


def _finite_history(name: str, res):
    bad = [k for k, v in res.history.items()
           if not bool(jnp.all(jnp.isfinite(jnp.asarray(v, jnp.float32))))]
    check(not bad, f"{name}: non-finite history series {bad}")


def _cohort_args(**kw):
    """cohort_train_loop arguments: Algorithm 1, int8 codec, one dispatch."""
    return dict(clients=COHORT_CLIENTS, participation=COHORT_S,
                rounds=COHORT_ROUNDS, log_every=COHORT_ROUNDS, codec="int8",
                seed=SEED, **kw)


def _feature_args(**kw):
    """feature_train_loop arguments: constrained Algorithm 4, one dispatch."""
    return dict(clients=FEATURE_CLIENTS, rounds=FEATURE_ROUNDS,
                constrained=True, log_every=FEATURE_ROUNDS, seed=SEED, **kw)


def cohort_phase():
    OUT.mkdir(parents=True, exist_ok=True)
    jsonl = OUT / "cohort.jsonl"
    jsonl.unlink(missing_ok=True)
    ir_dir = OUT / "cohort_ir"
    shutil.rmtree(ir_dir, ignore_errors=True)
    print(f"== cohort engine: Algorithm 1, I={COHORT_CLIENTS} S={COHORT_S} "
          f"rounds={COHORT_ROUNDS} codec=int8 impl=pallas", flush=True)
    # every program lowered for this run is dumped, so the scanned step's
    # own module can be searched for the compiled kernel
    jax.config.update("jax_dump_ir_to", str(ir_dir))
    try:
        t0 = time.perf_counter()
        kern = train.cohort_train_loop(codec_impl="pallas",
                                       log_jsonl=str(jsonl), **_cohort_args())
        print(f"cohort pallas run_s={time.perf_counter() - t0}", flush=True)
    finally:
        jax.config.update("jax_dump_ir_to", "")
    steps = [p.name for p in sorted(ir_dir.glob("*.mlir"))
             if all(s in p.read_text()
                    for s in ("stablehlo.while", "tpu_custom_call"))]
    print(f"cohort scanned modules with tpu_custom_call: {steps}", flush=True)
    check(bool(steps), "no scanned step module holds a tpu_custom_call: the "
          "Pallas quantizer did not compile into the round")
    rows = [json.loads(line) for line in jsonl.read_text().splitlines()]
    ts = [r["t"] for r in rows if r.get("kind") == "round"]
    print(f"cohort jsonl: {len(rows)} rows, {len(ts)} round rows", flush=True)
    check(ts == list(range(1, COHORT_ROUNDS + 1)),
          f"expected one JSONL row per round 1..{COHORT_ROUNDS}, got {ts}")
    _finite_history("cohort pallas", kern)
    t0 = time.perf_counter()
    ref = train.cohort_train_loop(codec_impl="ref", **_cohort_args())
    print(f"cohort ref run_s={time.perf_counter() - t0}", flush=True)
    compare_runs("cohort pallas vs ref codec", kern, ref)


def feature_phase():
    print(f"== feature engine: Algorithm 4, clients={FEATURE_CLIENTS} "
          f"rounds={FEATURE_ROUNDS}", flush=True)
    t0 = time.perf_counter()
    res = train.feature_train_loop(**_feature_args())
    print(f"feature run_s={time.perf_counter() - t0}", flush=True)
    for k in ("loss", "round_cons_viol", "nu", "round_nu"):
        check(k in res.history, f"feature history lacks {k}")
    _finite_history("feature", res)


def print_carry_shardings(name: str, state):
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        print(f"{name} carry {jax.tree_util.keystr(path)} "
              f"shape={tuple(leaf.shape)} sharding={leaf.sharding}",
              flush=True)


def four_chip_phase():
    common = _cohort_args(codec_impl="pallas")
    print(f"== cohort engine sharded vs local: I={COHORT_CLIENTS} "
          f"S={COHORT_S} over {jax.device_count()} chips", flush=True)
    sharded = train.cohort_train_loop(topology="sharded", **common)
    print_carry_shardings("cohort sharded", sharded.final_state)
    local = train.cohort_train_loop(topology="local", **common)
    compare_runs("cohort sharded vs local", sharded, local)

    fcommon = _feature_args(codec="int8", codec_impl="pallas")
    print(f"== feature engine sharded vs local: {FEATURE_CLIENTS} clients "
          f"on the model axis", flush=True)
    sharded = train.feature_train_loop(topology="sharded", **fcommon)
    print_carry_shardings("feature sharded", sharded.final_state)
    local = train.feature_train_loop(topology="local", **fcommon)
    compare_runs("feature sharded vs local", sharded, local)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-vs-local comparisons")
    args = ap.parse_args()
    dev = device_gate(args.chips)
    train.use_checkout_compile_cache()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase()
    else:
        lm_phase(dev)
        cohort_phase()
        feature_phase()
    print(f"all phases passed in {time.perf_counter() - t0} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
