"""Benchmark entry point — one section per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV. Scale via REPRO_BENCH_SCALE
(ci|paper); 'ci' keeps single-core runtime in minutes and records the
reduced (m, p) in every row.
"""
from __future__ import annotations

import sys
import traceback

from benchmarks.common import CSV


def main() -> None:
    csv = CSV()
    from benchmarks import (
        convergence_rate,
        fig_coeff_paths,
        fig_error_curves,
        fig_sparsity,
        kernels_bench,
        roofline_report,
        table4_baselines,
        table5_fw,
    )

    sections = [
        ("table4", table4_baselines.run),
        ("table5", table5_fw.run),
        ("fig12_coeff_paths", fig_coeff_paths.run),
        ("fig4_sparsity", fig_sparsity.run),
        ("fig_error_curves", fig_error_curves.run),
        ("prop2_convergence", convergence_rate.run),
        ("kernels", kernels_bench.run),
        ("roofline", roofline_report.run),
    ]
    failures = 0
    for name, fn in sections:
        print(f"# --- {name} ---", flush=True)
        try:
            fn(csv)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},0,ERROR={type(e).__name__}:{e}", flush=True)
            traceback.print_exc()
    print(f"# done: {len(csv.rows)} rows, {failures} section failures", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.devices import enable_compile_cache

    enable_compile_cache()
    main()
