"""Cohort cells: the program's Algorithm 1 step over the participant-only
engine (``core/algorithms.make_algorithm1_step(cohort=True)``) on the
program's own population (``data.synthetic.VirtualFedData``), driven by
``core/rounds.run_rounds`` K rounds per dispatch with the cohort loop's
eval probe between dispatches, as ``launch/train.cohort_train_loop`` runs
it. The codec is the one the traffic names, in the implementation the
program picks.

Set-up makes the weights from the seed and drives the one state object
through the window's first dispatch (K rounds, the window's own compiled
program and feed), which is also the warm-up. From it ``correct`` reads
the K per-round losses, round 1's surrogate step (the stationarity
residual the step reports) and the change of each leaf after K rounds. The
reference (bench/reference/mlp_fl.py, on its own copy of the population)
follows the same K rounds after the window.
"""
from __future__ import annotations

import gc

import numpy as np

import jax
import jax.numpy as jnp

from bench.lib import counts
from bench.lib.common import compare, seed_key
from bench.reference import mlp_fl
from bench.reference.population import Population

DATA_KEY = 0x5EED        # the population is fixed: one compilation serves every seed


def mlp_weights(key, features, hidden, classes):
    k0, k1 = jax.random.split(key)
    return {"w0": jax.random.normal(k0, (classes, hidden)) / jnp.sqrt(hidden),
            "w1": jax.random.normal(k1, (hidden, features)) / jnp.sqrt(features)}


def population_key():
    return jax.random.fold_in(jax.random.PRNGKey(DATA_KEY), 0xDA7A)


class Engine:
    unit = "rounds"

    def __init__(self, config, traffic, seed, devices, log):
        from repro.comm import error_feedback, make_codec
        from repro.configs import FLConfig
        from repro.core import algorithms, optimizer, rounds
        from repro.data.synthetic import VirtualFedData
        from repro.models import mlp

        self.tr, self.cfg, self.rounds = traffic, config, rounds
        self.k = traffic["rounds_per_dispatch"]
        self.s, self.b = traffic["participation"], traffic["batch"]
        self.fl = FLConfig(batch_size=self.b, constrained=False,
                           **traffic["fl"])
        self.data = VirtualFedData(
            population_key(), traffic["population"],
            num_features=config["num_features"],
            num_classes=config["num_classes"], noise=4.0)
        key = seed_key(seed)
        self.key_run0 = self.key_run = jax.random.fold_in(key, 2)
        dims = (config["num_features"], config["hidden"], config["num_classes"])
        self.params0 = jax.jit(mlp_weights, static_argnums=(1, 2, 3))(
            jax.random.fold_in(key, 1), *dims)
        self.p = counts.mlp_params(*dims)
        codec = make_codec(traffic["codec"])
        self.step = algorithms.make_algorithm1_step(
            mlp.per_sample_loss, self.data, self.fl, participation=self.s,
            codec=codec, cohort=True)
        self.state = optimizer.ssca_init(self.params0)
        if codec is not None:
            self.state = error_feedback.CommCarry(
                opt=self.state, ef=error_feedback.ef_store_init(
                    self.data.num_clients, self.p))
        # the cohort loop's O(1) eval probe: the first clients' shards
        eval_ids = jnp.arange(min(traffic["eval_clients"], self.data.num_clients),
                              dtype=jnp.int32)
        ez, ey, ec = self.data.shards_for(eval_ids)
        emask = (jnp.arange(ez.shape[1])[None, :] < ec[:, None]).astype(jnp.float32)

        def eval_fn(p, s):
            per_row = jax.vmap(lambda z, y: mlp.per_sample_loss(p, z, y))(ez, ey)
            return {"loss": float(jnp.sum(per_row * emask) / jnp.sum(emask))}

        self.eval_fn = eval_fn
        self.t = 1
        self.dispatch()                 # the window's first dispatch
        params = rounds.unwrap_comm(self.state).params
        self.prog = {
            "loss": [float(x) for x in self.last["round_loss_est"]],
            "surrogate": 2 * self.fl.tau * float(self.last["round_stat_res"][0]),
            "delta": {k: float(jnp.linalg.norm(params[k] - self.params0[k]))
                      for k in params},
            "params": {k: np.asarray(v) for k, v in params.items()}}
        log(f"first dispatch: losses {self.prog['loss'][0]} .. "
            f"{self.prog['loss'][-1]}")

    def dispatch(self) -> int:
        self.key_run, sub = jax.random.split(self.key_run)
        res = self.rounds.run_rounds(self.step, self.state, self.fl, sub,
                                     self.k, eval_fn=self.eval_fn,
                                     eval_every=self.k, t_start=self.t)
        self.state = res.final_state
        self.t += self.k
        self.last = res.history
        return self.k

    def losses(self):
        """The last dispatch's per-round losses, left on the device."""
        return self.last["round_loss_est"]

    def rounds_of(self, work: int) -> int:
        return work

    def module_texts(self):
        inputs = self.rounds.make_inputs(self.fl, 1, self.k,
                                         jax.random.PRNGKey(0))
        jitted = self.rounds._scan_jit(self.step)
        return [jitted.lower(self.state, inputs).compile().as_text()]

    def counts(self) -> dict:
        out = {"flops_per_round": counts.mlp_round_flops(self.p, self.s * self.b)}
        if self.tr["codec"] == "int8":
            out["encode_bytes_per_round"] = counts.int8_encode_bytes(self.s, self.p)
        return out

    def free(self):
        self.state = self.step = self.data = self.eval_fn = self.last = None
        gc.collect()

    def round_keys(self):
        """The keys of rounds 1..K as the first dispatch derived them
        (``run_rounds`` splits its key once more, ``make_inputs`` into K)."""
        sub = jax.random.split(self.key_run0)[1]
        return list(jax.random.split(jax.random.split(sub)[1], self.k))

    def reference(self, precision: str = "f32", fault=None) -> dict:
        pop = Population(population_key(), self.tr["population"],
                         self.cfg["num_features"], self.cfg["num_classes"])
        return mlp_fl.cohort_rounds(pop, self.params0, self.round_keys(),
                                    self.tr["fl"], self.s, self.b,
                                    self.tr["codec"] == "int8", precision,
                                    fault)

    def check(self) -> list:
        return compare(self.prog, self.reference(), self.tr["limits"])
