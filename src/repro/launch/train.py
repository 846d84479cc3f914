"""Training CLI: three federated jobs, each a thin builder over the one round
engine (``core/algorithms`` → ``core/fed`` → ``core/topology`` →
``core/rounds``, DESIGN.md §6/§11/§14). A job builds its population, client
loss and initial parameters; one shared body runs the algorithm and reports.

``--mode sample`` (default): cross-silo language-model training, the job
the benchmark's LM cell runs. A `data.synthetic.VirtualTokenData`
population of ``--clients`` silos, each holding a heavy-tailed number of
packed ``--seq``-token rows; ``--participation`` silos a round (at most
``--clients``), ``--batch`` rows per silo, a zoo decoder LM (``--arch``)
as the client model with ``models.transformer.per_sequence_loss`` as the
client loss, Algorithm 1 — or Algorithm 2 with ``--constrained`` (min
‖ω‖² s.t. mean loss <= ``--cost-limit``, formulation (40)) — through the
cohort engine.

``--mode cohort`` (DESIGN.md §14): million-client horizontal FL over a
`data.synthetic.VirtualFedData` population of ``--clients`` ragged,
Dirichlet-skewed MLP clients, ``--participation`` drawn a round; per-round
compute and state scale with the cohort, not the population.

``--mode feature`` (DESIGN.md §12): vertical FL, Algorithm 3 — or
Algorithm 4 with ``--constrained`` — on a synthetic classification task
whose features are split into ``--clients`` blocks.

Every job takes the same upload and run options: ``--codec`` compresses
the q-uploads with error feedback (DESIGN.md §10); ``--dp-epsilon``
clips and noises them before any codec (DESIGN.md §15); ``--topology
sharded`` spreads the clients over the host devices (DESIGN.md §11) — the
cohort for the sample-based jobs, the feature clients on the "model" axis
for the vertical one, each over the most devices that divide it;
``--log-jsonl`` streams per-round rows while the scan runs and writes a
run manifest beside them, ``--profile`` wraps the run in a profiler trace
(DESIGN.md §13); ``--ckpt`` saves the final parameters. ``--steps`` is the
round count.

CLI:  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
          --clients 8 --participation 4 --batch 2 --seq 128 --steps 20 \
          [--constrained] [--codec int8] [--topology sharded]
      PYTHONPATH=src python -m repro.launch.train --mode cohort \
          --clients 1000000 --participation 256 --steps 100
      PYTHONPATH=src python -m repro.launch.train --mode feature \
          --clients 4 --steps 200 [--constrained --cost-limit 1.2]
"""
from __future__ import annotations

import argparse
import contextlib
import os
import time
from pathlib import Path
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.comm import make_codec
from repro.configs import FLConfig, ModelConfig, get_config
from repro.core import privacy as privacy_lib
from repro.core import topology as topology_lib
from repro.obs import metrics as obs_metrics
from repro.obs import sinks as obs_sinks
from repro.obs import trace as obs_trace

# the families whose stack models.transformer.per_sequence_loss runs
LM_FAMILIES = ("dense", "moe", "vlm")


def use_checkout_compile_cache():
    """Entry-point setup of JAX's persistent compilation cache: a fixed
    ``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set,
    in which case JAX already uses that directory and nothing is changed.
    The path is fixed (never temporary, per-process or per-run) so that a
    later run from the same checkout finds what an earlier one compiled.
    Call it from ``main``-level code only: importing the library
    configures nothing."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(Path(__file__).resolve().parents[3]
                              / ".jax_cache"))


def _print_rows(history, log_every: int):
    """One line per ``log_every``-th round and the last: that round's
    per-round metrics, and the eval metrics where an eval ended there."""
    history = jax.device_get(history)
    n = len(history.get("round_t", ()))
    evals = {int(r): i for i, r in enumerate(history["round"])}
    series = {k: v for k, v in history.items()
              if k.startswith("round_") and k != "round_t"}
    for j in range(n):
        r = j + 1
        if r % log_every and r != n:
            continue
        line = {"round": r, **{k: float(v[j]) for k, v in series.items()}}
        if r in evals:
            line.update((k, float(v[evals[r]])) for k, v in history.items()
                        if not k.startswith("round"))
        print(" ".join(f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                       for k, v in line.items()), flush=True)


def _run_job(name: str, alg, args, *, key, rounds: int, log_every: int,
             config: dict, topology, summary: str, eval_fn=None,
             dp_rate: float = 1.0, dp_releases: int = 1,
             codec: Optional[str] = None, topk_frac: float = 0.01,
             codec_impl: str = "ref",
             dp: Optional[privacy_lib.DPConfig] = None,
             ckpt_path: Optional[str] = None, log_jsonl: Optional[str] = None,
             log_stream_every: int = 1, profile_dir: Optional[str] = None,
             **alg_kw):
    """The run-and-report body of every job: ``alg(*args, rounds, key')``
    on the engine (key' = ``fold_in(key, 2)``) under a metric stream (JSONL
    and a manifest when ``log_jsonl`` is set), host spans and the profiler
    (when ``profile_dir`` is set); then the rows, the "done" line and, when
    ``ckpt_path`` is set, a checkpoint of the final parameters. Returns
    the RunResult."""
    codec_obj = make_codec(codec, topk_frac=topk_frac, impl=codec_impl)
    stream = obs_metrics.MetricStream(
        [obs_sinks.JsonlSink(log_jsonl)] if log_jsonl else [],
        log_every=log_stream_every, name=name)
    spans = obs_trace.HostSpans(stream)
    prof = (obs_trace.profile(profile_dir) if profile_dir
            else contextlib.nullcontext())
    if log_jsonl:
        obs_sinks.write_manifest(
            log_jsonl + ".manifest.json", config=config, codec=codec_obj,
            topology=topology,
            extra=({"dp": privacy_lib.manifest_info(
                dp, dp_rate, rounds=rounds, releases_per_round=dp_releases)}
                if dp is not None else None))
    wall0 = time.time()
    with prof, spans.span("run", rounds=rounds):
        result = alg(*args, rounds, jax.random.fold_in(key, 2),
                     eval_fn=eval_fn, eval_every=log_every, codec=codec_obj,
                     topology=topology, obs=stream if log_jsonl else None,
                     dp=dp, **alg_kw)
    stream.close()
    _print_rows(result.history, log_every)
    shards = topology.num_shards if topology is not None else 1
    print(f"done: {rounds} rounds, {summary}, {shards} shard(s), "
          f"{time.time() - wall0:.1f}s", flush=True)
    if ckpt_path:
        from repro.checkpoint import save_checkpoint
        save_checkpoint(ckpt_path, result.params, step=rounds)
    return result


def _sample_job(name: str, loss, params0, data, key, *, participation: int,
                batch: int, constrained: bool, cost_limit: float,
                topology: str, fl: Optional[FLConfig], **run):
    """Algorithm 1 (Algorithm 2 when ``constrained``) through the cohort
    engine: ``participation`` of ``data``'s clients a round, ``batch`` rows
    each, ``loss(params, x, y)`` per row as the client loss. A sharded
    topology splits the cohort, so the population never constrains the
    mesh."""
    from repro.core import algorithms

    fl = fl or FLConfig(batch_size=batch, a1=0.9, a2=0.5, alpha_rho=0.1,
                        alpha_gamma=0.6, tau=0.2, l2_lambda=1e-5,
                        constrained=constrained, cost_limit=cost_limit,
                        penalty_c=1e4)
    alg = algorithms.algorithm2 if constrained else algorithms.algorithm1
    return _run_job(
        name, alg, (loss, params0, data, fl), key=key,
        topology=(topology_lib.sharded_for(participation)
                  if topology == "sharded" else None),
        summary=f"population {data.num_clients}, cohort {participation}",
        dp_rate=min(1.0, participation / data.num_clients),
        participation=participation, cohort=True, **run)


def train_loop(arch: Union[str, ModelConfig], *, clients: int = 8,
               participation: int = 8, rounds: int = 100, batch: int = 1,
               seq: int = 256, smoke: bool = False,
               constrained: bool = False, cost_limit: float = 3.0,
               topology: str = "local", log_every: int = 10, seed: int = 0,
               fl: Optional[FLConfig] = None, **run):
    """Cross-silo LM job: ``arch`` (a registry name or a `ModelConfig`,
    e.g. a depth-cut published config; ``smoke`` cuts it to the CPU size)
    as the client model over a `VirtualTokenData` population of
    ``clients`` silos, ``participation`` of them a round (at most
    ``clients``), ``batch`` rows of ``seq`` tokens per silo. ``run`` takes
    the options every job shares (codec, topk_frac, codec_impl, dp,
    ckpt_path, log_jsonl, log_stream_every, profile_dir). Returns the
    RunResult."""
    from repro.data.synthetic import VirtualTokenData
    from repro.models import transformer

    cfg = arch if isinstance(arch, ModelConfig) else get_config(arch)
    if smoke:
        cfg = cfg.smoke()
    if cfg.family not in LM_FAMILIES:
        raise ValueError(
            f"{cfg.name}: --mode sample trains a decoder LM (families "
            f"{', '.join(LM_FAMILIES)}); the {cfg.family!r} family has no "
            "per-sequence client loss yet (ROADMAP.md §2, reach item 7)")
    participation = min(participation, clients)
    key = jax.random.PRNGKey(seed)
    data = VirtualTokenData(jax.random.fold_in(key, 0xDA7A), clients, seq,
                            cfg.vocab_size)
    params0 = transformer.init(jax.random.fold_in(key, 1), cfg)

    def loss(params, tokens, targets):
        return transformer.per_sequence_loss(params, tokens, targets, cfg)

    config = {"mode": "sample", "arch": cfg.name, "clients": clients,
              "participation": participation, "rounds": rounds,
              "batch": batch, "seq": seq, "smoke": smoke,
              "constrained": constrained, "cost_limit": cost_limit,
              "seed": seed}
    return _sample_job(cfg.name, loss, params0, data, key,
                       participation=participation, batch=batch,
                       constrained=constrained, cost_limit=cost_limit,
                       topology=topology, fl=fl, rounds=rounds,
                       log_every=log_every, config=config, **run)


def cohort_train_loop(*, clients: int = 100_000, participation: int = 256,
                      rounds: int = 200, batch: int = 16, features: int = 32,
                      classes: int = 4, hidden: int = 16,
                      constrained: bool = False, cost_limit: float = 1.2,
                      topology: str = "local", log_every: int = 20,
                      seed: int = 0, fl: Optional[FLConfig] = None, **run):
    """Million-client horizontal FL job: a `VirtualFedData` population of
    ``clients`` ragged Dirichlet-skewed shards (never materialized — every
    row derives from the client id) with the paper's MLP as the client
    model; ``--clients 1000000 --participation 256`` runs on a laptop. An
    eval probe (the first 64 clients' shards, masked mean loss) runs every
    ``log_every`` rounds. ``run`` as in :func:`train_loop`. Returns the
    RunResult."""
    from repro.data.synthetic import VirtualFedData
    from repro.models import mlp

    key = jax.random.PRNGKey(seed)
    data = VirtualFedData(jax.random.fold_in(key, 0xDA7A), clients,
                          num_features=features, num_classes=classes,
                          noise=4.0)
    params0 = mlp.init(jax.random.fold_in(key, 1), features, hidden, classes)
    eval_ids = jnp.arange(min(64, clients), dtype=jnp.int32)
    ez, ey, ec = data.shards_for(eval_ids)
    emask = (jnp.arange(ez.shape[1])[None, :] < ec[:, None]).astype(jnp.float32)

    def eval_fn(p, s):
        per_row = jax.vmap(lambda z, y: mlp.per_sample_loss(p, z, y))(ez, ey)
        return {"loss": float(jnp.sum(per_row * emask) / jnp.sum(emask))}

    config = {"mode": "cohort", "clients": clients,
              "participation": participation, "rounds": rounds,
              "batch": batch, "features": features, "classes": classes,
              "hidden": hidden, "constrained": constrained,
              "cost_limit": cost_limit, "seed": seed}
    return _sample_job("cohort", mlp.per_sample_loss, params0, data, key,
                       participation=participation, batch=batch,
                       constrained=constrained, cost_limit=cost_limit,
                       topology=topology, fl=fl, rounds=rounds,
                       log_every=log_every, config=config, eval_fn=eval_fn,
                       **run)


def feature_train_loop(*, clients: int = 4, rounds: int = 200,
                       batch: int = 64, features: int = 128,
                       classes: int = 10, hidden: int = 32, n: int = 8000,
                       constrained: bool = False, cost_limit: float = 1.2,
                       topology: str = "local", log_every: int = 20,
                       seed: int = 0, fl: Optional[FLConfig] = None, **run):
    """Vertical-FL job: synthetic classification, features split into
    ``clients`` blocks, the MLP head composition (models/mlp.py), Algorithm
    3 or (constrained) Algorithm 4; the full-data loss is evaluated every
    ``log_every`` rounds. ``run`` as in :func:`train_loop`. Returns the
    RunResult."""
    from repro.core import algorithms, fed
    from repro.core.rounds import unwrap_comm
    from repro.data.synthetic import classification_dataset
    from repro.models import mlp

    key = jax.random.PRNGKey(seed)
    (z, y, _), _ = classification_dataset(key, n=n, num_features=features,
                                          num_classes=classes, test_n=10,
                                          noise=4.0)
    data = fed.partition_features(z, y, clients)
    pi = data.feature_blocks.shape[-1]
    params0 = {"w0": jax.random.normal(key, (classes, hidden)) * 0.2,
               "blocks": jax.random.normal(jax.random.fold_in(key, 1),
                                           (clients, hidden, pi)) * 0.2}
    fl = fl or FLConfig(batch_size=batch, a1=0.9, a2=0.5, alpha_rho=0.1,
                        alpha_gamma=0.6, tau=0.2, l2_lambda=1e-5,
                        mode="feature", constrained=constrained,
                        cost_limit=cost_limit, penalty_c=1e4)

    def eval_fn(p, s):
        hsum = sum(mlp.client_h(p["blocks"][i], data.feature_blocks[i])
                   for i in range(clients))
        m = {"loss": float(jnp.mean(mlp.per_sample_loss_from_h(p["w0"], hsum,
                                                                 y)))}
        if constrained:
            st = unwrap_comm(s)
            m["nu"], m["slack"] = float(st.nu), float(st.slack)
        return m

    config = {"mode": "feature", "clients": clients, "rounds": rounds,
              "batch": batch, "features": features, "classes": classes,
              "hidden": hidden, "n": n, "constrained": constrained,
              "cost_limit": cost_limit, "seed": seed}
    alg = algorithms.algorithm4 if constrained else algorithms.algorithm3
    return _run_job(
        "feature", alg,
        (mlp.per_sample_loss_from_h, mlp.client_h, params0, data, fl),
        key=key, rounds=rounds, log_every=log_every, config=config,
        topology=(topology_lib.feature_sharded_for(clients)
                  if topology == "sharded" else None),
        summary=f"{clients} feature clients", eval_fn=eval_fn,
        dp_releases=2, **run)


def main():
    """The CLI; returns the job's RunResult."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="client model from the zoo (required for --mode "
                         "sample; a decoder LM)")
    ap.add_argument("--mode", choices=("sample", "feature", "cohort"),
                    default="sample",
                    help="sample = cross-silo LM training over a virtual "
                         "token population (Alg 1/2); feature = vertical "
                         "FL, features split across clients (Alg 3/4, "
                         "DESIGN.md §12); cohort = million-client "
                         "horizontal FL over a virtual MLP population "
                         "(Alg 1/2, DESIGN.md §14)")
    ap.add_argument("--clients", type=int, default=4,
                    help="population size I: silos (sample), MLP clients "
                         "(cohort, e.g. 1000000 — never materialized) or "
                         "feature blocks (feature)")
    ap.add_argument("--participation", type=int, default=256,
                    help="sample and cohort modes: clients drawn a round, "
                         "S (at most --clients in sample mode); per-round "
                         "state and compute scale with S")
    ap.add_argument("--features", type=int, default=128)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--n", type=int, default=8000)
    ap.add_argument("--cost-limit", type=float, default=1.2,
                    help="U in min ‖ω‖² s.t. loss <= U (with --constrained)")
    ap.add_argument("--steps", type=int, default=100, help="rounds")
    ap.add_argument("--batch", type=int, default=8,
                    help="rows per client a round (sample, cohort); the "
                         "global batch (feature)")
    ap.add_argument("--seq", type=int, default=256,
                    help="tokens per row (sample)")
    ap.add_argument("--smoke", action="store_true",
                    help="cut --arch to its CPU-size variant")
    ap.add_argument("--constrained", action="store_true")
    ap.add_argument("--codec", choices=("none", "int8", "int4", "topk"),
                    default="none")
    ap.add_argument("--topk-frac", type=float, default=0.01)
    ap.add_argument("--codec-impl", choices=("ref", "pallas"), default="ref",
                    help="quantizer backend: pure-jnp ref, or the fused "
                         "Pallas quantize-dequantize kernel (TPU)")
    ap.add_argument("--topology", choices=("local", "sharded"),
                    default="local",
                    help="client execution engine (DESIGN.md §11): local = "
                         "one device; sharded = the clients split over the "
                         "most host devices that divide the cohort (or the "
                         "feature clients), aggregated by psum")
    ap.add_argument("--dp-epsilon", type=float, default=None, metavar="EPS",
                    help="enable DP on the q-uploads (DESIGN.md §15): "
                         "per-release (ε, δ) target for the analytic "
                         "Gaussian calibration; the streamed dp_epsilon "
                         "metric and the manifest report the composed "
                         "cross-round ε from the subsampled-RDP accountant")
    ap.add_argument("--dp-delta", type=float, default=1e-5, metavar="DELTA",
                    help="DP δ (with --dp-epsilon; default 1e-5)")
    ap.add_argument("--dp-clip", type=float, default=1.0, metavar="C",
                    help="DP ℓ2 clip norm of each client's mean upload "
                         "(with --dp-epsilon; default 1.0)")
    ap.add_argument("--ckpt", default=None, metavar="PATH",
                    help="save the final parameters to PATH")
    ap.add_argument("--log-jsonl", default=None, metavar="PATH",
                    help="stream round/eval/span rows to PATH as JSONL while "
                         "the scan runs (obs/ subsystem, DESIGN.md §13); a "
                         "run manifest is written to PATH.manifest.json")
    ap.add_argument("--log-every", type=int, default=1, metavar="N",
                    help="emit every N-th streamed round row (default 1)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="jax.profiler trace of the whole run into DIR "
                         "(phase-annotated; open with xprof/perfetto)")
    args = ap.parse_args()
    if args.mode == "sample" and args.arch is None:
        ap.error("--arch is required for --mode sample")
    use_checkout_compile_cache()
    dp = (privacy_lib.DPConfig(clip_norm=args.dp_clip,
                               epsilon=args.dp_epsilon, delta=args.dp_delta)
          if args.dp_epsilon is not None else None)
    common = dict(rounds=args.steps, batch=args.batch,
                  constrained=args.constrained, cost_limit=args.cost_limit,
                  topology=args.topology, codec=args.codec,
                  topk_frac=args.topk_frac, codec_impl=args.codec_impl,
                  dp=dp, ckpt_path=args.ckpt, log_jsonl=args.log_jsonl,
                  log_stream_every=args.log_every, profile_dir=args.profile)
    if args.mode == "feature":
        return feature_train_loop(clients=args.clients,
                                  features=args.features,
                                  classes=args.classes, hidden=args.hidden,
                                  n=args.n, **common)
    if args.mode == "cohort":
        return cohort_train_loop(clients=args.clients,
                                 participation=args.participation,
                                 features=args.features,
                                 classes=args.classes, hidden=args.hidden,
                                 **common)
    return train_loop(args.arch, clients=args.clients,
                      participation=args.participation, seq=args.seq,
                      smoke=args.smoke, **common)


if __name__ == "__main__":
    main()
