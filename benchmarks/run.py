"""Benchmark driver: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus section markers).
  PYTHONPATH=src python -m benchmarks.run [--quick]
"""
import argparse
import sys


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.parse_args()

    from . import (fig2_contribution, fig5_transfer, fig6_rms, figS1_cost,
                   figS2_montecarlo, figS3_doa, kernel_bench)

    print("name,us_per_call,derived")
    sections = [
        ("fig2 (contribution analysis)", fig2_contribution.run),
        ("fig5 (transfer function / INL)", fig5_transfer.run),
        ("fig6 (C-MAC RMS error + energy)", fig6_rms.run),
        ("figS1 (area/latency/power vs baselines)", figS1_cost.run),
        ("figS2 (Monte-Carlo mismatch)", figS2_montecarlo.run),
        ("figS3 (DOA application)", figS3_doa.run),
        ("kernels (emulation fidelity/speed)", kernel_bench.run),
    ]
    failures = []
    for name, fn in sections:
        print(f"# --- {name} ---")
        try:
            fn()
        except Exception as e:  # keep the suite running; report at the end
            failures.append((name, repr(e)))
            print(f"# FAILED: {name}: {e!r}")
    from .common import write_bench_json
    write_bench_json()  # idempotent: flush whatever rows were recorded
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
