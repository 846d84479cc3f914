"""Constrained federated training of a language model — the paper's Algorithm 2
applied to the model zoo: min ‖ω‖² s.t. mean-loss <= U (formulation (40)),
over a virtual population of ``--clients`` token silos, ``--participation``
of them a round, through the CLI's sample job (``launch.train.train_loop``).

    PYTHONPATH=src python examples/constrained_lm_finetune.py \
        --arch qwen2.5-3b --smoke --steps 120 --cost-limit 4.5

Shows the constrained SSCA dynamics on a transformer: the dual ν activates
while the loss is above U, then the iterate rides the constraint boundary
while the parameter norm shrinks (Theorem 2 behaviour on a real model).
"""
import argparse

import jax
import jax.numpy as jnp

from repro.launch.train import train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--participation", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--cost-limit", type=float, default=4.5)
    args = ap.parse_args()

    res = train_loop(args.arch, clients=args.clients,
                     participation=args.participation, rounds=args.steps,
                     batch=args.batch, seq=args.seq, smoke=args.smoke,
                     constrained=True, cost_limit=args.cost_limit,
                     log_every=10)
    h = res.history
    loss = float(h["round_loss_est"][-1])
    l2 = float(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                   for x in jax.tree.leaves(res.params)))
    print(f"\nfinal: loss={loss:.4f} (U={args.cost_limit}) "
          f"nu={float(h['round_nu'][-1]):.3f} "
          f"slack={float(h['round_slack'][-1]):.2e} l2={l2:.2f}")
    if loss <= args.cost_limit * 1.1:
        print("constraint satisfied — model norm minimized subject to the "
              "loss budget.")
    else:
        print("constraint not yet met — increase --steps or U.")


if __name__ == "__main__":
    main()
