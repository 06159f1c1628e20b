"""Benchmark harness — one module per paper table/figure (+ beyond-paper).
Prints ``name,us_per_call,derived`` CSV rows."""
from __future__ import annotations

import sys


def main() -> None:
    from . import (
        bench_fig2_latency,
        bench_jax_vs_python,
        bench_roofline,
        bench_screen,
        bench_sim_utilization,
        bench_tables,
    )
    from .common import use_compile_cache

    use_compile_cache()

    print("name,us_per_call,derived")
    bench_tables.run()            # paper Tables 3-6 (correctness + latency)
    bench_fig2_latency.run()      # paper Fig. 2 (3 schedulers x scenarios)
    bench_screen.run()            # stage-1 screen microbenchmark (PR 3)
    bench_jax_vs_python.run()     # beyond-paper vectorized scheduler
    bench_sim_utilization.run()   # backfill utilization (paper motivation)
    bench_roofline.run()          # dry-run roofline table (deliverable g)


if __name__ == "__main__":
    main()
