# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: paper tables/figures + beyond-paper LM-proxy + roofline.

REPRO_BENCH_SCALE  (default small): scale for proxy tuning targets.
REPRO_BENCH_EVAL_SCALE (default full): scale for original-vs-proxy evaluation.
REPRO_BENCH_FAST=1: skip the expensive full-scale evaluations.
"""

import os
import sys
import traceback


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import paper_tables as pt
    from .compile_vs_run import bench_compile_vs_run
    from .lm_proxy import bench_lm_proxy
    from .roofline import bench_roofline

    fast = os.environ.get("REPRO_BENCH_FAST", "") == "1"
    benches = [
        ("table1_coverage", pt.bench_table1_coverage),
        ("compile_vs_run", bench_compile_vs_run),
        ("table6_speedup", pt.bench_table6_speedup),
        ("fig5_accuracy", pt.bench_fig5_accuracy),
        ("fig6_instruction_mix", pt.bench_fig6_instruction_mix),
        ("fig7_io", pt.bench_fig7_io),
        ("fig8_9_data_impact", pt.bench_fig8_9_data_impact),
        ("fig11_scaling", pt.bench_fig11_scaling),
        ("fig12_cross_platform", pt.bench_fig12_cross_platform),
        ("lm_proxy", bench_lm_proxy),
        ("roofline", bench_roofline),
    ]
    if fast:
        benches = [b for b in benches
                   if b[0] in ("table1_coverage", "compile_vs_run",
                               "roofline")]
    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches:
        try:
            for row in fn():
                print(row, flush=True)
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            print(f"{name},0.0,ERROR:{type(e).__name__}:{e}", flush=True)
            failed.append(name)
    if failed:
        # every bench still prints its row, but the harness must not rot
        # silently — CI's smoke step keys off this exit code
        print(f"benchmark errors in: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
