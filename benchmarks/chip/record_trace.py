#!/usr/bin/env python3
"""Record the small chip trace that ``test_selfcheck.py`` reduces: one
admission drain of 64 requests on a 640-host t1-12k5 fleet, profiled, with
what is known of it without the reduction (one drain module, one screen
kernel call per decision row) written beside it.

    python3 benchmarks/chip/record_trace.py <out_dir>
"""
from __future__ import annotations

import gzip
import json
import os
import shutil
import sys

import fleetgen
import run
import served


def main(out_dir: str) -> int:
    started = run.start_jax(False, 1)
    if started is None:
        return 2
    jax, _ = started
    repro = run.import_program(jax)
    config = dict(run.load_json(run.BENCH, "configs", "t1-12k5.json"),
                  hosts=run.REHEARSE_HOSTS)
    fleet = fleetgen.prefill(config, 1)
    arr = fleetgen.arrivals(config, 0.02, 256, 1)
    policy = run.policy_of(repro, config, False)
    served.warm_up(repro, fleet, arr, policy, 64)
    s = served.Served(repro, fleet, arr, policy, trace=True)
    jax.block_until_ready(s.fleet.state)
    tmp = os.path.join(run.BENCH, ".cache", "record")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    for j in range(64):
        s.submit(j)
    s.drain(float(arr.t[63]))
    s.absorb()
    jax.profiler.stop_trace()
    path = next(os.path.join(d, f) for d, _, fs in os.walk(tmp) for f in fs
                if f.endswith(".xplane.pb"))
    os.makedirs(out_dir, exist_ok=True)
    with open(path, "rb") as src, gzip.open(
            os.path.join(out_dir, "drain64.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    rows = config["policy"]["admit_batch"]
    with open(os.path.join(out_dir, "drain64.expect.json"), "w") as f:
        json.dump({"drain_modules": 1, "screen_calls": rows,
                   "decisions": len(s.drains[0])}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
