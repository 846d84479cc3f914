"""The training CLI at smoke sizes: each job runs in-process through
``main()`` to finite losses, and the sample job is Algorithm 1 on the
cohort engine bit for bit."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import FLConfig, get_config
from repro.core import algorithms
from repro.data.synthetic import VirtualTokenData
from repro.launch import train
from repro.models import transformer

LM = ["--arch", "qwen2.5-3b", "--smoke", "--clients", "4",
      "--participation", "2", "--batch", "2", "--seq", "16"]


@pytest.mark.parametrize("argv", [
    LM,
    LM + ["--constrained", "--cost-limit", "3.0"],
    ["--mode", "cohort", "--clients", "1000", "--participation", "8",
     "--batch", "4"],
    ["--mode", "feature", "--clients", "2", "--n", "500", "--batch", "16"],
], ids=["sample", "sample-constrained", "cohort", "feature"])
def test_main_runs_each_job(argv, monkeypatch, capsys):
    monkeypatch.setattr(train, "use_checkout_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", ["train", "--steps", "2", *argv])
    res = train.main()
    losses = np.asarray(res.history["round_loss_est"])
    assert losses.shape == (2,) and np.all(np.isfinite(losses)), losses
    out = capsys.readouterr().out.splitlines()
    assert out[-2].startswith("round=2 ") and out[-1].startswith("done: 2 "), out


def test_sample_job_is_algorithm1_on_the_cohort_engine():
    """train_loop's population, initial params and run key, handed to
    algorithms.algorithm1(cohort=True) directly, give the same bits."""
    cfg = get_config("qwen2.5-3b").smoke()
    res = train.train_loop(cfg, clients=4, participation=2, rounds=2,
                           batch=2, seq=16, seed=1)
    key = jax.random.PRNGKey(1)
    data = VirtualTokenData(jax.random.fold_in(key, 0xDA7A), 4, 16,
                            cfg.vocab_size)
    fl = FLConfig(batch_size=2, a1=0.9, a2=0.5, alpha_rho=0.1,
                  alpha_gamma=0.6, tau=0.2, l2_lambda=1e-5, cost_limit=3.0,
                  penalty_c=1e4)
    ref = algorithms.algorithm1(
        lambda p, x, y: transformer.per_sequence_loss(p, x, y, cfg),
        transformer.init(jax.random.fold_in(key, 1), cfg), data, fl, 2,
        jax.random.fold_in(key, 2), participation=2, cohort=True)
    assert jax.tree.structure(res.params) == jax.tree.structure(ref.params)
    for a, b in zip(jax.tree.leaves(res.params), jax.tree.leaves(ref.params)):
        assert bool(jnp.array_equal(a, b))
    assert np.array_equal(res.history["round_loss_est"],
                          ref.history["round_loss_est"])
