"""Distributed training: the SSCA federated optimizer wrapped around any zoo
model under pjit. The per-round client upload/aggregate of Algorithm 1/2 is
realized by the data-axis all-reduce that pjit inserts for the batch-mean
gradient (clients = data shards, equal N_i; see DESIGN.md §2/§7).

The single-host driver is scan-compiled (DESIGN.md §6): batch selection,
gradient, and the SSCA update for a whole log interval run as ONE ``lax.scan``
dispatch via core/rounds.py, with the ρ^t/γ^t schedules threaded as scan
inputs. ``--driver loop`` keeps the seed's one-dispatch-per-step execution
for comparison (benchmarks/rounds_bench.py quantifies the gap).

Upload compression (DESIGN.md §10): ``--codec {none,int8,int4,topk}`` runs
the round's gradient "upload" through a repro.comm codec with an
error-feedback residual carried in the scan state (CommCarry) — in the
clients-as-data-shards picture this compresses exactly what Algorithm 1's
clients put on the wire, and the logged ``upload_bytes`` is the per-round
wire cost from repro.comm.accounting.

Client topology (DESIGN.md §11): ``--topology sharded`` makes the
clients-as-data-shards picture *explicit* — the per-round batch is split
into ``--shards`` equal client shards distributed over a 1-D device mesh via
core/topology.py's shard_map engine, each shard computes its local gradient
(and codec/EF compresses it at the client boundary), and the Algorithm-1
aggregation is a weighted psum over the mesh. ``--topology local`` (default)
keeps the single-dispatch pjit picture unchanged.

Feature-based (vertical FL) mode (DESIGN.md §12): ``--mode feature`` runs
Algorithm 3 — or Algorithm 4 with ``--constrained`` (min ‖ω‖² s.t.
mean-loss <= ``--cost-limit``, formulation (40)) — on a synthetic
classification task with the features split into ``--clients`` vertical
blocks. ``--topology sharded`` places each feature client on its own
"model"-axis shard (`launch.mesh.make_feature_mesh`) with the h-exchange
as a tiled all_gather; the codec flags compress the head + block q-uploads
exactly as in core/algorithms.py.

Observability (DESIGN.md §13): ``--log-jsonl out.jsonl`` streams per-round
rows (loss, stationarity residual, upload bytes, ...) to disk WHILE the scan
runs via the obs/ MetricStream tap, writes a run manifest (config, mesh,
codec, per-dispatch HLO cost) next to it, and interleaves host-span timing
rows; ``--log-every N`` thins the stream; ``--profile DIR`` wraps the run in
a jax.profiler trace whose timeline carries the protocol phase annotations.

Cohort mode (DESIGN.md §14): ``--mode cohort --clients 1000000
--participation 256`` runs horizontal FL over a VIRTUAL population — client
shards derived on the fly from the client id (`data.synthetic
.VirtualFedData`), the S-client cohort drawn in O(S) by a keyed Feistel
permutation, EF residuals in a keyed store gathered/scattered per round —
so per-round compute and state scale with S while I goes to a million.

Differential privacy (DESIGN.md §15): ``--dp-epsilon 4 [--dp-delta 1e-5
--dp-clip 1.0]`` clips + Gaussian-noises every gradient upload at the
client boundary BEFORE the codec (analytic Gaussian calibration), streams
dp_epsilon (the subsampled-RDP accountant's composed ε-so-far), clip
fraction, and noise norm per round, and records the full accounting in the
run manifest. Works in every mode; cohort mode's S-of-I draw earns the
subsampling amplification.

CLI:  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
          --steps 100 --batch 8 --seq 512 [--constrained] [--smoke] \
          [--driver scan|loop] [--codec int8] [--topk-frac 0.01] \
          [--codec-impl pallas] [--topology local|sharded] [--shards 8] \
          [--log-jsonl out.jsonl --log-every 1 --profile prof/]
      PYTHONPATH=src python -m repro.launch.train --mode feature \
          --clients 4 --steps 200 [--constrained --cost-limit 1.2] \
          [--topology sharded] [--codec int8] [--driver scan|loop]
      PYTHONPATH=src python -m repro.launch.train --mode cohort \
          --clients 1000000 --participation 256 --steps 100 \
          [--constrained] [--codec int8] [--topology sharded]
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
from jax.sharding import PartitionSpec as P

from repro.comm import (CommCarry, ef_init, ef_init_stacked, ef_roundtrip,
                        flatten_tree, make_codec, tree_flat_dim,
                        with_comm_carry)
from repro.configs import FLConfig, ModelConfig, get_config
from repro.core import optimizer, rounds
from repro.core import privacy as privacy_lib
from repro.core import topology as topology_lib
from repro.launch import mesh as mesh_lib
from repro.models import get_model
from repro.obs import metrics as obs_metrics
from repro.obs import sinks as obs_sinks
from repro.obs import trace as obs_trace


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


def _make_stream(log_jsonl, log_stream_every, profile_dir, name):
    """Observability trio for a training loop: MetricStream (JSONL when
    ``log_jsonl`` is set), HostSpans bound to it, and the profiler context
    (nullcontext unless ``profile_dir``). Always returns a live stream so
    span rows have somewhere to go; with no sinks it is just an in-memory
    row buffer."""
    sinks = [obs_sinks.JsonlSink(log_jsonl)] if log_jsonl else []
    stream = obs_metrics.MetricStream(sinks, log_every=log_stream_every,
                                      name=name)
    spans = obs_trace.HostSpans(stream)
    prof = (obs_trace.profile(profile_dir) if profile_dir
            else contextlib.nullcontext())
    return stream, spans, prof


def _ssca_update(state, loss, grads, fl: FLConfig, rho_t, gamma_t,
                 constrained: bool):
    """Shared update + metrics of the (constrained) train step — single
    definition so the codec path below cannot drift from the dense one."""
    if constrained:
        new = optimizer.ssca_constrained_step(state, grads, loss, fl,
                                              rho_t=rho_t, gamma_t=gamma_t)
        return new, {"loss": loss, "nu": new.nu, "slack": new.slack,
                     "l2": sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                               for x in jax.tree.leaves(new.params))}
    new = optimizer.ssca_step(state, grads, fl, rho_t=rho_t, gamma_t=gamma_t)
    return new, {"loss": loss, "t": state.t}


def make_train_step(model, cfg, fl: FLConfig):
    """Returns train_step(state, batch[, rho_t, gamma_t]) -> (state, metrics).
    Unconstrained Algorithm-1-example update (= momentum SGD w/ diminishing
    stepsizes). rho_t/gamma_t default to the state.t-derived schedule; the
    scan driver passes them precomputed per round."""

    def train_step(state, batch, rho_t=None, gamma_t=None):
        loss, grads = jax.value_and_grad(model.loss_fn)(state.params, batch, cfg)
        return _ssca_update(state, loss, grads, fl, rho_t, gamma_t,
                            constrained=False)

    return train_step


def make_constrained_train_step(model, cfg, fl: FLConfig):
    """Algorithm-2-example: min ‖ω‖² s.t. mean-loss <= U (formulation (40))."""

    def train_step(state, batch, rho_t=None, gamma_t=None):
        loss, grads = jax.value_and_grad(model.loss_fn)(state.params, batch, cfg)
        return _ssca_update(state, loss, grads, fl, rho_t, gamma_t,
                            constrained=True)

    return train_step


def state_specs(model, cfg, constrained: bool):
    ps = model.param_specs(cfg, mode="train")
    if constrained:
        return optimizer.SSCAConstrainedState(
            params=ps,
            cons=optimizer.QuadSurrogate(d=P(), g=ps),
            t=P(), nu=P(), slack=P())
    return optimizer.SSCAState(params=ps, g=ps, t=P())


def batch_specs(batch_tree, mesh):
    axes = mesh_lib.data_axes(mesh)
    return jax.tree.map(lambda _: P(axes), batch_tree)


def jit_train_step(model, cfg, fl, mesh, batch_like, constrained=False):
    step = (make_constrained_train_step if constrained else make_train_step)(
        model, cfg, fl)
    sspec = mesh_lib.named(mesh, state_specs(model, cfg, constrained))
    bspec = mesh_lib.named(mesh, batch_specs(batch_like, mesh))
    return jax.jit(step, in_shardings=(sspec, bspec),
                   out_shardings=(sspec, None))


# ---------------------------------------------------------------------------
# single-host training driver (CPU-runnable with reduced configs)
# ---------------------------------------------------------------------------


def make_scanned_step(model, cfg, fl: FLConfig, tokens, batch: int, seq: int,
                      constrained: bool = False, codec=None, topology=None,
                      dp=None):
    """Fuses per-round data selection into the train step so the whole round
    chain is scannable: step(state, RoundInputs) -> (state, metrics). With a
    codec, the gradient is compressed through an error-feedback roundtrip
    before the SSCA update and the state is a CommCarry.

    With a sharded ``topology`` the batch is reshaped into D equal client
    shards and the gradient (+ loss) estimate is computed by the topology
    engine — per-shard value_and_grad, per-shard codec/EF (residuals become
    an (D, P) matrix in the CommCarry), equal-weight 1/D psum aggregation.
    The local path is byte-identical to before.

    ``dp=`` (privacy.DPConfig) clips+noises the gradient upload(s) before
    any codec encode (DESIGN.md §15) — per shard on the sharded path, on
    the single all-reduced gradient on the local path — and adds the dp_*
    metrics (all shards release every round, so the accountant runs at
    q = 1)."""
    from repro.data.synthetic import sample_window

    eps_fn = privacy_lib.make_eps_fn(dp, 1.0) if dp is not None else None
    shards = getattr(topology, "num_shards", 1) if topology is not None else 1
    if topology is not None and topology.name == "sharded":
        if batch % shards:
            raise ValueError(f"--batch {batch} must be divisible by the "
                             f"{shards} client shards of --topology sharded")

        def sharded_body(state, inp, ef):
            data = sample_window(tokens, inp.key, batch, seq)
            shard = jax.tree.map(
                lambda x: x.reshape((shards, batch // shards) + x.shape[1:]),
                data)

            def client_fn(b):
                loss, grads = jax.value_and_grad(model.loss_fn)(
                    state.params, b, cfg)
                return grads, loss

            ckeys = (jax.random.split(jax.random.fold_in(inp.key, 0xC0DEC),
                                      shards) if codec is not None else None)
            dkeys = (jax.random.split(jax.random.fold_in(inp.key, 0xD9),
                                      shards) if dp is not None else None)
            w = jnp.full((shards,), 1.0 / shards, jnp.float32)
            s = topology.weighted_sum(client_fn, (shard,), w, codec=codec,
                                      ef=ef, codec_keys=ckeys, dp=dp,
                                      dp_keys=dkeys)
            new, metrics = _ssca_update(state, s.value, s.weighted, fl,
                                        inp.rho, inp.gamma, constrained)
            if codec is not None:
                metrics["upload_bytes"] = float(
                    shards * codec.nbytes(tree_flat_dim(state.params)))
            if dp is not None:
                metrics["dp_epsilon"] = eps_fn(inp.t)
                metrics["dp_clip_frac"] = jnp.mean(s.dp["clipped"])
                metrics["dp_noise_norm"] = jnp.sqrt(
                    jnp.sum(s.dp["noise_sq"]))
            return new, s.ef, metrics

        return with_comm_carry(codec, sharded_body)

    train_step = (make_constrained_train_step if constrained
                  else make_train_step)(model, cfg, fl)

    def step(state, inp):
        data = sample_window(tokens, inp.key, batch, seq)
        return train_step(state, data, rho_t=inp.rho, gamma_t=inp.gamma)

    if codec is None and dp is None:
        return step

    def comm_body(state, inp, ef):
        data = sample_window(tokens, inp.key, batch, seq)
        loss, grads = jax.value_and_grad(model.loss_fn)(state.params, data,
                                                        cfg)
        gf, unflatten = flatten_tree(grads)
        metrics_dp = None
        if dp is not None:
            gf, dstats = privacy_lib.privatize_flat(
                gf, jax.random.fold_in(inp.key, 0xD9), dp)
            metrics_dp = {"dp_epsilon": eps_fn(inp.t),
                          "dp_clip_frac": dstats["clipped"],
                          "dp_noise_norm": jnp.sqrt(dstats["noise_sq"])}
        if codec is not None:
            _, g_hat, new_ef = ef_roundtrip(
                codec, gf, ef, jax.random.fold_in(inp.key, 0xC0DEC))
        else:
            g_hat, new_ef = gf, ef
        new, metrics = _ssca_update(state, loss, unflatten(g_hat), fl,
                                    inp.rho, inp.gamma, constrained)
        if codec is not None:
            metrics["upload_bytes"] = float(codec.nbytes(gf.shape[0]))
        if metrics_dp is not None:
            metrics.update(metrics_dp)
        return new, new_ef, metrics

    return with_comm_carry(codec, comm_body)


def train_loop(arch: Union[str, ModelConfig], steps: int, batch: int,
               seq: int, *,
               smoke: bool = False, constrained: bool = False,
               fl: Optional[FLConfig] = None, log_every: int = 10,
               ckpt_path: Optional[str] = None, seed: int = 0,
               driver: str = "scan", codec: Optional[str] = None,
               topk_frac: float = 0.01, codec_impl: str = "ref",
               topology: str = "local", shards: Optional[int] = None,
               log_jsonl: Optional[str] = None, log_stream_every: int = 1,
               profile_dir: Optional[str] = None,
               dp: Optional[privacy_lib.DPConfig] = None):
    """Train a zoo LM with the SSCA optimizer; ``arch`` is a registry name
    or a `ModelConfig` (e.g. a depth-cut published config). Returns the
    final state and one log row per ``log_every``-step dispatch."""
    from repro.data.synthetic import token_dataset

    if isinstance(arch, ModelConfig):
        cfg, arch = arch, arch.name
    else:
        cfg = get_config(arch)
    if smoke:
        cfg = cfg.smoke()
    fl = fl or FLConfig(a1=0.9, a2=0.5, alpha_rho=0.1, alpha_gamma=0.6,
                        tau=0.2, l2_lambda=1e-5, cost_limit=3.0)
    model = get_model(cfg)
    key = jax.random.PRNGKey(seed)
    params = model.init(key, cfg)
    state = (optimizer.ssca_constrained_init(params) if constrained
             else optimizer.ssca_init(params))
    topo = topology_lib.make_topology(
        topology, mesh=(mesh_lib.make_client_mesh(shards)
                        if topology == "sharded" else None))
    codec_obj = make_codec(codec, topk_frac=topk_frac, impl=codec_impl)
    if codec_obj is not None:
        dim = tree_flat_dim(params)
        ef0 = (ef_init_stacked(topo.num_shards, dim)
               if topo.name == "sharded" else ef_init(dim))
        state = topo.place_state(CommCarry(opt=state, ef=ef0))

    toks = token_dataset(jax.random.fold_in(key, 1), cfg.vocab_size,
                         n_tokens=max(200_000, batch * (seq + 1) * 4))
    step_fn = make_scanned_step(model, cfg, fl, toks, batch, seq, constrained,
                                codec=codec_obj, topology=topo, dp=dp)
    engine = rounds.ENGINES[driver]
    sizes = rounds.chunk_sizes(steps, log_every)

    stream, spans, prof = _make_stream(log_jsonl, log_stream_every,
                                       profile_dir, name=arch)
    if log_jsonl:
        from repro.roofline.analysis import jit_cost_summary
        probe = jax.tree.map(
            lambda x: x[0],
            rounds.make_inputs(fl, 1, 1, jax.random.fold_in(key, 3)))
        obs_sinks.write_manifest(
            log_jsonl + ".manifest.json",
            config={"arch": arch, "steps": steps, "batch": batch, "seq": seq,
                    "constrained": constrained, "driver": driver,
                    "smoke": smoke, "seed": seed},
            codec=codec_obj, topology=topo,
            cost=jit_cost_summary(step_fn, state, probe),
            extra=({"dp": privacy_lib.manifest_info(dp, 1.0, rounds=steps)}
                   if dp is not None else None))

    logs = []
    t0, done = 1, 0
    key_run = jax.random.fold_in(key, 2)
    wall0 = time.time()
    with prof:
        for size in sizes:
            key_run, sub = jax.random.split(key_run)
            inputs = rounds.make_inputs(fl, t0, size, sub)
            with spans.span("dispatch", rounds=size, t0=t0):
                state, ms = stream.run(step_fn, state, inputs, driver=driver) \
                    if log_jsonl else engine(step_fn, state, inputs)
            t0 += size
            done += size
            m = {k: float(v[-1]) for k, v in ms.items()}
            m["step"] = done
            m["wall_s"] = time.time() - wall0
            logs.append(m)
            print(" ".join(f"{k}={v:.4g}" if isinstance(v, float)
                           else f"{k}={v}" for k, v in m.items()), flush=True)
    if ckpt_path:
        from repro.checkpoint import save_checkpoint
        save_checkpoint(ckpt_path, rounds.unwrap_comm(state).params,
                        step=steps)
    stream.close()
    return state, logs


# ---------------------------------------------------------------------------
# feature-based (vertical FL) training driver — Algorithms 3/4 on the shared
# topology + scan engine (DESIGN.md §12)
# ---------------------------------------------------------------------------


def feature_train_loop(*, clients: int = 4, rounds: int = 200,
                       batch: int = 64, features: int = 128,
                       classes: int = 10, hidden: int = 32, n: int = 8000,
                       constrained: bool = False, cost_limit: float = 1.2,
                       topology: str = "local", codec: Optional[str] = None,
                       topk_frac: float = 0.01, codec_impl: str = "ref",
                       driver: str = "scan", log_every: int = 20,
                       seed: int = 0, fl: Optional[FLConfig] = None,
                       log_jsonl: Optional[str] = None,
                       log_stream_every: int = 1,
                       profile_dir: Optional[str] = None,
                       dp: Optional[privacy_lib.DPConfig] = None):
    """Vertical-FL driver: synthetic classification, features split into
    `clients` blocks, MLP head composition (models/mlp.py), Algorithm 3 or
    (constrained) Algorithm 4 via run_feature_rounds. Returns the RunResult.
    """
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
    topo = (topology_lib.feature_sharded_for(clients)
            if topology == "sharded" else None)
    codec_obj = make_codec(codec, topk_frac=topk_frac, impl=codec_impl)

    def eval_fn(p, s):
        hsum = sum(mlp.client_h(p["blocks"][i], data.feature_blocks[i])
                   for i in range(clients))
        loss = float(jnp.mean(mlp.per_sample_loss_from_h(p["w0"], hsum, y)))
        m = {"loss": loss}
        if constrained:
            m["nu"], m["slack"] = float(s_nu(s)), float(s_slack(s))
        return m

    def s_nu(s):
        return unwrap_comm(s).nu

    def s_slack(s):
        return unwrap_comm(s).slack

    alg = algorithms.algorithm4 if constrained else algorithms.algorithm3
    stream, spans, prof = _make_stream(log_jsonl, log_stream_every,
                                       profile_dir, name="feature")
    if log_jsonl:
        obs_sinks.write_manifest(
            log_jsonl + ".manifest.json",
            config={"mode": "feature", "clients": clients, "rounds": rounds,
                    "batch": batch, "features": features, "classes": classes,
                    "hidden": hidden, "n": n, "constrained": constrained,
                    "cost_limit": cost_limit, "driver": driver, "seed": seed},
            codec=codec_obj, topology=topo,
            extra=({"dp": privacy_lib.manifest_info(
                dp, 1.0, rounds=rounds, releases_per_round=2)}
                if dp is not None else None))
    wall0 = time.time()
    with prof, spans.span("run", rounds=rounds):
        result = alg(mlp.per_sample_loss_from_h, mlp.client_h, params0, data,
                     fl, rounds, jax.random.fold_in(key, 2), eval_fn=eval_fn,
                     eval_every=log_every, driver=driver, codec=codec_obj,
                     topology=topo, obs=stream if log_jsonl else None, dp=dp)
    stream.close()
    for i, r in enumerate(result.history["round"]):
        line = {k: float(v[i]) for k, v in result.history.items()
                if not k.startswith("round")}
        line["round"] = int(r)
        print(" ".join(f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                       for k, v in line.items()), flush=True)
    shards = topo.num_shards if topo is not None else 1
    print(f"done: {rounds} rounds, {shards} client shard(s), "
          f"{time.time() - wall0:.1f}s", flush=True)
    return result


# ---------------------------------------------------------------------------
# cohort-engine (million-client horizontal FL) training driver — DESIGN.md §14
# ---------------------------------------------------------------------------


def cohort_train_loop(*, clients: int = 100_000, participation: int = 256,
                      rounds: int = 200, batch: int = 16, features: int = 32,
                      classes: int = 4, hidden: int = 16,
                      constrained: bool = False, cost_limit: float = 1.2,
                      topology: str = "local", codec: Optional[str] = None,
                      topk_frac: float = 0.01, codec_impl: str = "ref",
                      driver: str = "scan", log_every: int = 20,
                      seed: int = 0, fl: Optional[FLConfig] = None,
                      log_jsonl: Optional[str] = None,
                      log_stream_every: int = 1,
                      profile_dir: Optional[str] = None,
                      dp: Optional[privacy_lib.DPConfig] = None):
    """Million-client horizontal FL driver: a `VirtualFedData` population of
    ``clients`` ragged Dirichlet-skewed shards (never materialized — every
    row derives from the client id), Algorithm 1 (or 2 with --constrained)
    through the participant-only O(S) cohort engine. Per-round compute,
    uploads, and EF state scale with ``participation``, not ``clients`` —
    ``--clients 1000000 --participation 256`` runs on a laptop. Returns the
    RunResult."""
    from repro.core import algorithms
    from repro.data.synthetic import VirtualFedData
    from repro.models import mlp

    key = jax.random.PRNGKey(seed)
    data = VirtualFedData(jax.random.fold_in(key, 0xDA7A), clients,
                          num_features=features, num_classes=classes,
                          noise=4.0)
    params0 = mlp.init(jax.random.fold_in(key, 1), features, hidden, classes)
    fl = fl or FLConfig(batch_size=batch, a1=0.9, a2=0.5, alpha_rho=0.1,
                        alpha_gamma=0.6, tau=0.2, l2_lambda=1e-5,
                        constrained=constrained, cost_limit=cost_limit,
                        penalty_c=1e4)
    # a sharded topology splits the COHORT over devices — the population
    # size never constrains the mesh fit
    topo = (topology_lib.sharded_for(participation)
            if topology == "sharded" else None)
    codec_obj = make_codec(codec, topk_frac=topk_frac, impl=codec_impl)

    # fixed O(1)-sized eval probe: the first 64 clients' shards, masked mean
    eval_ids = jnp.arange(min(64, clients), dtype=jnp.int32)
    ez, ey, ec = data.shards_for(eval_ids)
    emask = (jnp.arange(ez.shape[1])[None, :] < ec[:, None]).astype(jnp.float32)

    def eval_fn(p, s):
        per_row = jax.vmap(lambda z, y: mlp.per_sample_loss(p, z, y))(ez, ey)
        return {"loss": float(jnp.sum(per_row * emask) / jnp.sum(emask))}

    alg = algorithms.algorithm2 if constrained else algorithms.algorithm1
    stream, spans, prof = _make_stream(log_jsonl, log_stream_every,
                                       profile_dir, name="cohort")
    if log_jsonl:
        obs_sinks.write_manifest(
            log_jsonl + ".manifest.json",
            config={"mode": "cohort", "clients": clients,
                    "participation": participation, "rounds": rounds,
                    "batch": batch, "features": features, "classes": classes,
                    "hidden": hidden, "constrained": constrained,
                    "cost_limit": cost_limit, "driver": driver, "seed": seed},
            codec=codec_obj, topology=topo,
            extra=({"dp": privacy_lib.manifest_info(
                dp, min(1.0, participation / clients), rounds=rounds)}
                if dp is not None else None))
    wall0 = time.time()
    with prof, spans.span("run", rounds=rounds):
        result = alg(mlp.per_sample_loss, params0, data, fl, rounds,
                     jax.random.fold_in(key, 2), eval_fn=eval_fn,
                     eval_every=log_every, participation=participation,
                     driver=driver, codec=codec_obj, topology=topo,
                     obs=stream if log_jsonl else None, cohort=True, dp=dp)
    stream.close()
    for i, r in enumerate(result.history["round"]):
        line = {k: float(v[i]) for k, v in result.history.items()
                if not k.startswith("round")}
        line["round"] = int(r)
        print(" ".join(f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                       for k, v in line.items()), flush=True)
    shards = topo.num_shards if topo is not None else 1
    print(f"done: {rounds} rounds, population {clients}, cohort "
          f"{participation} over {shards} shard(s), "
          f"{time.time() - wall0:.1f}s", flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="model zoo arch (required for --mode sample)")
    ap.add_argument("--mode", choices=("sample", "feature", "cohort"),
                    default="sample",
                    help="sample = horizontal FL on a zoo model (Alg 1/2); "
                         "feature = vertical FL, features split across "
                         "clients (Alg 3/4, DESIGN.md §12); cohort = "
                         "million-client horizontal FL through the "
                         "participant-only O(S) engine over a virtual "
                         "population (DESIGN.md §14)")
    ap.add_argument("--clients", type=int, default=4,
                    help="feature-mode vertical client count, or cohort-mode "
                         "population size I (e.g. 1000000 — never "
                         "materialized)")
    ap.add_argument("--participation", type=int, default=256,
                    help="cohort-mode per-round cohort size S (per-round "
                         "state and compute scale with S, not --clients)")
    ap.add_argument("--features", type=int, default=128)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--n", type=int, default=8000)
    ap.add_argument("--cost-limit", type=float, default=1.2,
                    help="U in min ‖ω‖² s.t. loss <= U (feature mode "
                         "--constrained)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--constrained", action="store_true")
    ap.add_argument("--driver", choices=("scan", "loop"), default="scan")
    ap.add_argument("--codec", choices=("none", "int8", "int4", "topk"),
                    default="none")
    ap.add_argument("--topk-frac", type=float, default=0.01)
    ap.add_argument("--codec-impl", choices=("ref", "pallas"), default="ref",
                    help="quantizer backend: pure-jnp ref, or the fused "
                         "Pallas quantize-dequantize kernel (TPU)")
    ap.add_argument("--topology", choices=("local", "sharded"),
                    default="local",
                    help="client execution engine (DESIGN.md §11): local = "
                         "single-device; sharded = clients-as-batch-shards "
                         "over a device mesh via shard_map + psum")
    ap.add_argument("--shards", type=int, default=None,
                    help="client-shard count for --topology sharded "
                         "(default: all host devices; must divide --batch)")
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
    ap.add_argument("--ckpt", default=None)
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
    use_checkout_compile_cache()
    dp = (privacy_lib.DPConfig(clip_norm=args.dp_clip,
                               epsilon=args.dp_epsilon, delta=args.dp_delta)
          if args.dp_epsilon is not None else None)
    if args.mode == "cohort":
        cohort_train_loop(clients=args.clients,
                          participation=args.participation,
                          rounds=args.steps, batch=args.batch,
                          features=args.features, classes=args.classes,
                          hidden=args.hidden, constrained=args.constrained,
                          cost_limit=args.cost_limit,
                          topology=args.topology, codec=args.codec,
                          topk_frac=args.topk_frac,
                          codec_impl=args.codec_impl, driver=args.driver,
                          log_jsonl=args.log_jsonl,
                          log_stream_every=args.log_every,
                          profile_dir=args.profile, dp=dp)
        return
    if args.mode == "feature":
        feature_train_loop(clients=args.clients, rounds=args.steps,
                           batch=args.batch, features=args.features,
                           classes=args.classes, hidden=args.hidden,
                           n=args.n, constrained=args.constrained,
                           cost_limit=args.cost_limit,
                           topology=args.topology, codec=args.codec,
                           topk_frac=args.topk_frac,
                           codec_impl=args.codec_impl, driver=args.driver,
                           log_jsonl=args.log_jsonl,
                           log_stream_every=args.log_every,
                           profile_dir=args.profile, dp=dp)
        return
    if args.arch is None:
        ap.error("--arch is required for --mode sample")
    train_loop(args.arch, args.steps, args.batch, args.seq, smoke=args.smoke,
               constrained=args.constrained, ckpt_path=args.ckpt,
               driver=args.driver, codec=args.codec,
               topk_frac=args.topk_frac, codec_impl=args.codec_impl,
               topology=args.topology, shards=args.shards,
               log_jsonl=args.log_jsonl, log_stream_every=args.log_every,
               profile_dir=args.profile, dp=dp)


if __name__ == "__main__":
    main()
