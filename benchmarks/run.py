"""Benchmark harness — one function per paper table/figure plus the kernel
micro-benches. Prints ``name,us_per_call,derived`` CSV.

Usage:  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
"""
import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark names")
    args = ap.parse_args()

    from repro.launch.train import use_checkout_compile_cache
    use_checkout_compile_cache()

    from benchmarks import (dp_bench, extensions_bench, figures,
                            kernels_bench, obs_bench, rounds_bench,
                            scale_bench)
    benches = [
        ("rounds_scan_vs_loop", rounds_bench.rounds_scan_vs_loop),
        ("scale_cohort_engine", scale_bench.scale_smoke),
        ("obs_stream_overhead", obs_bench.obs_overhead),
        ("fig1_unconstrained_sample_based", figures.fig1_unconstrained_sample_based),
        ("fig1ef_constrained_sample_based", figures.fig1ef_constrained_sample_based),
        ("fig2_feature_based", figures.fig2_feature_based),
        ("fig3_comm_comp_tradeoff", figures.fig3_comm_comp_tradeoff),
        ("fig4_sparsity_cost_tradeoff", figures.fig4_sparsity_cost_tradeoff),
        ("ext1_local_updates", extensions_bench.ext1_local_updates),
        ("ext2_dp_uploads", extensions_bench.ext2_dp_uploads),
        ("dp_privacy_frontier", dp_bench.dp_privacy_frontier),
        ("kernel_microbench", kernels_bench.kernel_microbench),
    ]
    failed = []
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        try:
            fn()
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except AssertionError as e:
            failed.append(name)
            print(f"# {name} CLAIM-CHECK FAILED: {e}", flush=True)
        except Exception as e:
            failed.append(name)
            print(f"# {name} ERROR: {type(e).__name__}: {e}", flush=True)
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)
    print("# all benchmarks passed")


if __name__ == '__main__':
    main()
