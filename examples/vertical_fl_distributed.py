"""Vertical (feature-based) FL on a device mesh: Algorithms 3/4 through the
shared topology + scan engine (DESIGN.md §12) — each feature client resident
on its own "model"-axis shard, the paper's step-4 h-exchange as a tiled
all_gather, and K rounds compiled to one dispatch.

    PYTHONPATH=src python examples/vertical_fl_distributed.py --clients 4
    PYTHONPATH=src python examples/vertical_fl_distributed.py --clients 4 \
        --constrained --cost-limit 1.2 --codec int8

Uses virtual host devices so it runs anywhere; on a real cluster the same
code maps clients onto physical chips. ``--topology local`` runs the same
mathematics as a single-device vmap — the trajectories agree bit-for-bit
(tests/test_feature_topology.py pins it).
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=300)
    ap.add_argument("--topology", choices=("sharded", "local"),
                    default="sharded")
    ap.add_argument("--constrained", action="store_true",
                    help="run Algorithm 4: min ‖ω‖² s.t. loss <= U (40)")
    ap.add_argument("--cost-limit", type=float, default=1.2,
                    help="U for --constrained")
    ap.add_argument("--codec", choices=("none", "int8", "int4", "topk"),
                    default="none",
                    help="compress the head + block q-uploads")
    args = ap.parse_args()

    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={args.clients}")

    from repro.launch.train import feature_train_loop

    print(f"{args.clients} feature clients, topology={args.topology}"
          + (", constrained (Algorithm 4)" if args.constrained
             else " (Algorithm 3)"))
    result = feature_train_loop(
        clients=args.clients, rounds=args.rounds,
        constrained=args.constrained, cost_limit=args.cost_limit,
        topology=args.topology, codec=args.codec,
        log_every=max(args.rounds // 10, 1))
    print("h-exchange per round: (I x B x J) floats all-gathered over the "
          "model axis (the paper's Alg-3 step 4); "
          f"axis bytes/round = {float(result.history['round_axis_bytes'][0]):.0f}")


if __name__ == "__main__":
    main()
