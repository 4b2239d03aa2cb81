"""Benchmark harness: one function per paper table/figure + roofline.

Prints ``name,us_per_call,derived``-style CSV blocks:
  table2      paper Table II (scalability: N, gamma, alpha vs DR)
  fig7        paper Fig. 7(a)/(b): FPS and FPS/W vs ROBIN/LIGHTBULB,
              with gmean ratios against the paper's published numbers
  fig7_sens   calibration-knob sensitivity of the prior-work gap
  kernel      XNOR-popcount GEMM microbenchmarks
  roofline    per (arch x shape) roofline terms from the dry-run
"""
from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import bnn_ablation, fig7_comparison, kernel_bench, \
        roofline, table2_scalability
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    sections = [
        ("table2", table2_scalability.run),
        ("fig7", fig7_comparison.run),
        ("fig7_sensitivity", fig7_comparison.run_sensitivity),
        ("kernel", kernel_bench.run),
        ("roofline", roofline.run),
        ("bnn_ablation", bnn_ablation.run),
    ]
    failures = 0
    for name, fn in sections:
        print(f"# ==== {name} ====", flush=True)
        try:
            for line in fn():
                print(line)
        except Exception as e:  # keep the harness going; report at exit
            failures += 1
            print(f"# {name} FAILED: {e!r}")
        print(flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
