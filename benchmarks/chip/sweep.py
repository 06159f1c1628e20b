#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: the same seeded fleet and trace served
at a series of wall rates in one process (no reference replay), printing
for each rate the latency percentiles, the requests due and not yet
decided over the window, refusals and how late the generator ran.  The
knee is the highest rate at which that count does not grow through the
window.

    python3 benchmarks/chip/sweep.py --workload t1-12k5.open --seed 11 \\
        --seconds 10 --rates 250 500 1000 2000
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import fleetgen
import run
import served


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = run.cell_of(spec, args.workload)
    config = run.load_json(run.BENCH, "configs", f"{cell['config']}.json")
    if args.rehearse:
        config = dict(config, hosts=run.REHEARSE_HOSTS)
    started = run.start_jax(args.rehearse, cell["chips"])
    if started is None:
        return 2
    jax, devices = started
    repro = run.import_program(jax)
    fleet = fleetgen.prefill(config, args.seed)
    sim_rate = fleetgen.little_rate(config, fleet)
    policy = run.policy_of(repro, config, args.rehearse)
    count = int(max(args.rates) * (args.seconds * 1.05 + 5)) + 256
    arr = fleetgen.arrivals(config, sim_rate, count, args.seed)
    served.warm_up(repro, fleet, arr, policy, config["policy"]["admit_batch"])
    for r in args.rates:
        s = served.Served(repro, fleet, arr, policy, trace=False)
        jax.block_until_ready(s.fleet.state)
        t = time.perf_counter()
        due, n, w0, late = served.run_open(s, r / sim_rate, args.seconds, 5.0)
        lat = s.decided_at[:n] - (w0 + due[:n])
        lat[s.overflow[:n] | np.isnan(lat)] = np.inf
        fin = np.sort(lat[np.isfinite(lat)])
        ws = [w for _, w in s.outstanding]
        half = len(ws) // 2
        st = s.front.stats
        print(json.dumps({
            "rate": r, "due": n, "p50_ms": float(np.median(fin) * 1e3) if fin.size else None,
            "p95_ms": run.p95_ms(lat), "p99_ms": float(np.percentile(fin, 99) * 1e3) if fin.size else None,
            "undecided_or_full": int(np.sum(~np.isfinite(lat))),
            "outstanding_first_half_mean": float(np.mean(ws[:half])) if half else None,
            "outstanding_second_half_mean": float(np.mean(ws[half:])) if ws else None,
            "outstanding_series": s.outstanding,
            "late_p50_ms": float(np.nanmedian(late[:n]) * 1e3),
            "late_max_ms": float(np.nanmax(late[:n]) * 1e3),
            "drains": st.drains, "admitted": st.admitted, "refused": st.rejected_retry,
            "queue_full": st.rejected_overflow, "wall_s": time.perf_counter() - t,
        }), flush=True)
        del s
    return 0


if __name__ == "__main__":
    sys.exit(main())
