#!/usr/bin/env python3
"""Record the small chip traces that the self-checks reduce:

* one admission drain of 64 requests on a 640-host t1-12k5 fleet on one
  chip, with what is known of it without the reduction (one drain module,
  one screen kernel call per decision row) written beside it
  (``test_selfcheck.py``);
* with ``--chips 4``: one ``schedule_batch`` job of 64 requests on a
  4 096-host sharded-1m fleet sharded over four chips, with what is known
  of it (one ``schedule_many`` module of 64 rows on each chip, an
  ``all-gather`` in the compiled program) and the batch readers' readings
  of it (``test_chips4.py``).

    python3 benchmarks/chip/record_trace.py <out_dir> [--chips 4]
"""
from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import types

import fleetgen
import program_trace
import readers
import run
import served
import tracing

#: the four-chip fixture: its hosts, and the requests of its one traced job
BATCH4_HOSTS = 4096
BATCH4_JOB = 64


def batch4_readings(trace_dir: str, shape, device_kind: str):
    """The batch readers on a recorded four-chip trace of one job."""
    ctx = types.SimpleNamespace(
        summary=tracing.summarize(trace_dir, 1.0), trace_dir=trace_dir,
        traced_decisions=BATCH4_JOB, traced_rows=BATCH4_JOB, shape=shape,
        device_kind=device_kind, note=[])
    return {
        "many_device_us": readers.many_device_us(ctx),
        "screen_roofline": readers.sharded_screen_roofline(ctx),
        "collective_us": readers.collective_us(ctx),
        "device_idle": readers.device_idle(ctx),
        "host_ms": readers.batch_host_ms(ctx),
        "stage2_device_ms": program_trace.stage2_device_ms(ctx),
    }, ctx.note


def _gzip_newest(tmp: str, dst_path: str) -> None:
    path = next(os.path.join(d, f) for d, _, fs in os.walk(tmp) for f in fs
                if f.endswith(".xplane.pb"))
    with open(path, "rb") as src, gzip.open(dst_path, "wb") as dst:
        shutil.copyfileobj(src, dst)


def record_batch4(out_dir: str) -> int:
    """One job through ``schedule_batch`` on a fleet sharded over four
    chips, profiled, after a warm-up job of the same shape."""
    import batch

    started = run.start_jax(False, 4)
    if started is None:
        return 2
    jax, devices = started
    repro = run.import_program(jax)
    config = dict(run.load_json(run.BENCH, "configs", "sharded-1m.json"),
                  hosts=BATCH4_HOSTS)
    traffic = run.load_json(run.BENCH, "traffic", "t1-batch.json")
    fleet = fleetgen.prefill(config, 1)
    policy = run.policy_of(repro, config, False, 4)
    jobs = fleetgen.batch_jobs(config, dict(traffic, job_sizes=[BATCH4_JOB]),
                               0.1, 256, 1)
    b = batch.Batch(repro, fleet, jobs, policy, trace=True)
    b.run_job(0)
    jax.block_until_ready(b.fleet.state)
    hlo = many_program(b.fleet, BATCH4_JOB)
    tmp = os.path.join(run.BENCH, ".cache", "record")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    b.run_job(1)
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    _gzip_newest(tmp, os.path.join(out_dir, "batch4.xplane.pb.gz"))
    shape = (b.fleet.state.n_hosts // 4, fleet.k_slots, fleet.cap.shape[0],
             b.fleet.shortlist_stats["shortlist"])
    got, notes = batch4_readings(tmp, shape, devices[0].device_kind)
    want = {"chips": 4, "many_modules": 4, "rows": BATCH4_JOB,
            "decisions": BATCH4_JOB, "all_gather_in_program": "all-gather" in hlo,
            "shape": list(shape), "device_kind": devices[0].device_kind,
            "readings": got}
    with open(os.path.join(out_dir, "batch4.expect.json"), "w") as f:
        json.dump(want, f, indent=1)
    print(json.dumps(want), "\n".join(notes), sep="\n")
    return 0


def many_program(fleet, rows: int) -> str:
    """The compiled text of ``schedule_many`` for ``rows`` requests on
    ``fleet``'s state and policy (the program a job of that size runs)."""
    import jax
    import numpy as np

    from repro.core import jax_scheduler

    d = fleet.state.free_f.shape[1]
    spec = jax.ShapeDtypeStruct
    rows_of = [spec((rows, d), np.float32), spec((rows,), np.bool_),
               spec((rows,), np.int32), spec((rows,), np.float32),
               spec((rows,), np.float32), spec((rows,), np.int32),
               spec((rows,), np.float32), spec((rows,), np.int32)]
    return jax_scheduler._many_donated.lower(
        fleet.state, *rows_of, policy=fleet.policy).compile().as_text()


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
    os.makedirs(out_dir, exist_ok=True)
    _gzip_newest(tmp, os.path.join(out_dir, "drain64.xplane.pb.gz"))
    rows = config["policy"]["admit_batch"]
    with open(os.path.join(out_dir, "drain64.expect.json"), "w") as f:
        json.dump({"drain_modules": 1, "screen_calls": rows,
                   "decisions": len(s.drains[0])}, f)
    return 0


if __name__ == "__main__":
    if sys.argv[2:] == ["--chips", "4"]:
        sys.exit(record_batch4(sys.argv[1]))
    sys.exit(main(sys.argv[1]))
