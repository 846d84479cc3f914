"""chip_smoke.py's own logic, checked on the CPU: its float32 reference
rebuilds the LM job's first round (cohort, rows, weights, initial params)
exactly, and it
refuses to run (exit non-zero, no result line) where JAX finds no TPU."""
import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config
from repro.launch import train

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


@pytest.mark.parametrize("seed", [0, 3])
def test_reference_loss_matches_train_loop_first_step(seed):
    """Both sides compute in f32 at a smoke size, so they agree far inside
    the tolerance; another cohort, batch or init would move the loss by
    ~1e-2 (the round-to-round spread at this size)."""
    cfg = dataclasses.replace(get_config(chip_smoke.LM_ARCH).smoke(),
                              remat=True)
    res = train.train_loop(cfg, clients=4, participation=2, rounds=2,
                           batch=2, seq=16, seed=seed)
    ref = chip_smoke.lm_reference_loss(cfg, 4, 2, 2, 16, 2, seed=seed)
    assert float(res.history["round_loss_est"][0]) == pytest.approx(
        ref, rel=1e-5)


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert run.returncode != 0
    assert "needs a TPU" in run.stderr
    assert '"ok"' not in run.stdout and "==" not in run.stdout
