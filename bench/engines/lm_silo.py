"""LM silo cells: a DeepSeek-V3-block language model (Moonlight-16B-A3B,
cut to this chip's share) as the client model of the program's Algorithm 1
over cross-silo clients, through the cohort engine
(``core/algorithms.make_algorithm1_step(cohort=True)``) at full
participation, on the program's virtual token population
(``data.synthetic.VirtualTokenData``), K rounds per dispatch through
``core/rounds.run_rounds``. Each dispatch ends on the device (the state is
waited for), so a window counts finished rounds.

Set-up makes the weights from the seed (every matrix N(0, 0.02^2), norms
at weight 1) and drives the state through the window's first dispatch,
which is also the warm-up. From it ``correct`` reads the K per-round
losses, round 1's surrogate step and the change of each leaf after K
rounds (the final weights are kept too, for ``bench/calibrate.py``); the
reference (bench/reference/moonlight.py, float32, on its own copy of the
population) follows the same K rounds after the window, one silo at a
time.
"""
from __future__ import annotations

import dataclasses
import gc

import numpy as np

import jax
import jax.numpy as jnp

from bench.lib import moe_counts
from bench.lib.common import compare, seed_key
from bench.lib.trace import scopes_from_hlo
from bench.reference import moonlight

DATA_KEY = 0x5EED        # the population is fixed: one compilation serves every seed
INIT_STD = 0.02


def population_key():
    return jax.random.fold_in(jax.random.PRNGKey(DATA_KEY), 0x70C5)


def model_config(c: dict):
    """The program's Moonlight ModelConfig with every shape from
    configuration file ``c``."""
    from repro.configs.registry import get_config
    return dataclasses.replace(
        get_config("moonlight-16b-a3b"),
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], n_experts=c["router_experts"],
        experts_per_token=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        experts_held=c["n_routed_experts"], expert_shard=c["expert_shard"],
        n_shared_experts=c["n_shared_experts"],
        first_dense_layers=c["first_k_dense_replace"],
        router_scoring=c["scoring_func"],
        routed_scaling=c["routed_scaling_factor"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], tie_embeddings=c["tie_word_embeddings"],
        dtype=c["dtype"])


def weights(key, mc):
    """Program-layout weights: each matrix N(0, INIT_STD^2) from its own
    fold of ``key``, each norm scale 0 (weight 1)."""
    from repro.models import transformer
    shapes = jax.eval_shape(lambda: transformer.init(jax.random.PRNGKey(0), mc))
    paths, tree = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = [jnp.zeros(s.shape, s.dtype)
              if str(getattr(path[-1], "key", "")) == "scale"
              else INIT_STD * jax.random.normal(jax.random.fold_in(key, i),
                                                s.shape, s.dtype)
              for i, (path, s) in enumerate(paths)]
    return jax.tree_util.tree_unflatten(tree, leaves)


def flat(tree):
    return {"/".join(str(getattr(k, "key", k)) for k in path): v
            for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


class Engine:
    unit = "rounds"

    def __init__(self, config, traffic, seed, devices, log):
        from repro.configs import FLConfig
        from repro.core import algorithms, optimizer, rounds
        from repro.data.synthetic import VirtualTokenData
        from repro.models import transformer
        from repro.obs.trace import PHASES

        self.tr, self.cfg, self.rounds, self.phases = traffic, config, rounds, PHASES
        self.k = traffic["rounds_per_dispatch"]
        self.s, self.b = traffic["participation"], traffic["batch"]
        self.fl = FLConfig(batch_size=self.b, constrained=False,
                           **traffic["fl"])
        self.mc = mc = model_config(config)
        self.data = VirtualTokenData(
            population_key(), traffic["population"], traffic["seq_len"],
            mc.vocab_size, **traffic["sizes"])
        key = seed_key(seed)
        self.key_init = jax.random.fold_in(key, 1)
        self.key_run0 = self.key_run = jax.random.fold_in(key, 2)
        params = jax.jit(weights, static_argnums=1)(self.key_init, mc)
        self.tree = jax.tree.structure(params)
        self.params0 = jax.device_get(flat(params))     # host copy, by leaf
        self.step = algorithms.make_algorithm1_step(
            lambda p, z, y: transformer.per_sequence_loss(p, z, y, mc),
            self.data, self.fl, participation=self.s, cohort=True)
        self.state = optimizer.ssca_init(params)
        del params
        self.t, self.slots = 1, []
        self.dispatch()                 # the window's first dispatch
        h = self.last
        final = jax.device_get(flat(self.state.params))
        start = self.params0
        self.prog = {
            "loss": [float(x) for x in h["round_loss_est"]],
            "surrogate": 2 * self.fl.tau * float(h["round_stat_res"][0]),
            "delta": {k: float(np.linalg.norm(final[k] - start[k]))
                      for k in final},
            "params": final,
            "moe_dropped": int(np.sum(h["round_moe_dropped"])),
            "moe_slots_held": [int(x) for x in h["round_moe_slots_held"]]}
        self.slots = []                 # the window's held slots, per dispatch
        log(f"first dispatch: losses {self.prog['loss']}, held slots "
            f"{self.prog['moe_slots_held']}, load max "
            f"{[float(x) for x in h['round_moe_load_max']]}")

    def dispatch(self) -> int:
        self.key_run, sub = jax.random.split(self.key_run)
        res = self.rounds.run_rounds(self.step, self.state, self.fl, sub,
                                     self.k, t_start=self.t)
        self.state = jax.block_until_ready(res.final_state)
        self.t += self.k
        self.last = res.history
        self.slots.append(jnp.sum(res.history["round_moe_slots_held"]))
        return self.k

    def losses(self):
        """The last dispatch's per-round losses, left on the device."""
        return self.last["round_loss_est"]

    def rounds_of(self, work: int) -> int:
        return work

    def _window_text(self):
        inputs = self.rounds.make_inputs(self.fl, 1, self.k,
                                         jax.random.PRNGKey(0))
        jitted = self.rounds.scan_jit_for(self.step, self.state)
        return jitted.lower(self.state, inputs).compile().as_text()

    def module_texts(self):
        self.text = self._window_text()
        return [self.text]

    def counts(self) -> dict:
        """Model FLOPs per round from shapes, the window program's
        {instruction: scope} map under the program's own scopes, and the
        token-slots the held experts computed since set-up."""
        c = self.cfg
        tokens = self.s * self.b * self.tr["seq_len"]
        text = getattr(self, "text", None) or self._window_text()
        return {"flops_per_round": tokens * moe_counts.
                moonlight_train_flops_per_token(c, self.tr["seq_len"]),
                "scope_map": scopes_from_hlo(text, self.phases)[1],
                "moe_slots_held": float(sum(float(x) for x in self.slots)),
                "expert_gmm_flops_per_slot": moe_counts.expert_gmm_flops(1, c)}

    def free(self):
        self.state = self.step = self.data = self.last = None
        gc.collect()

    def round_keys(self):
        """The keys of rounds 1..K as the first dispatch derived them."""
        sub = jax.random.split(self.key_run0)[1]
        return list(jax.random.split(jax.random.split(sub)[1], self.k))

    def reference(self, precision: str = "f32", fault=None) -> dict:
        silos = moonlight.Silos(population_key(), self.tr["population"],
                                self.tr["seq_len"], self.cfg["vocab_size"],
                                **self.tr["sizes"])
        params0 = jax.tree.unflatten(self.tree, list(self.params0.values()))
        return moonlight.cohort_rounds(silos, params0, self.round_keys(),
                                       self.tr["fl"], self.s, self.b,
                                       self.cfg, precision, fault)

    def check(self) -> list:
        return checks(self.prog, self.reference(), self.tr["limits"])


def checks(prog, refr, limits) -> list:
    """``common.compare``'s numbers and the slots the program dropped (0)."""
    out = compare(prog, refr, limits)
    out.append(("moe_dropped", prog["moe_dropped"], 0,
                "routed held slots the experts did not compute"))
    return out
