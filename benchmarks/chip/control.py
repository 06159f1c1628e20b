#!/usr/bin/env python3
"""The control of the benchmark's check: the plain reference computed with
bfloat16 termination costs, put in the program's place, has to come out
as not correct.  For each seed this runs the cell as ``run.py`` does (at
the cell's own size and load, for ``--seconds``) and prints one JSON line
with the numbers the check compares, read twice: for the program, and for
the control answering from the same states, beside the run's end-to-end
readings (its window is a benchmark run's).  Further arguments go to
``run.py`` as they are (``--rehearse``, ``--chips``).  It is not part of a
benchmark run.

    python3 benchmarks/chip/control.py --workload t1-12k5.open \\
        --seconds 10 --seeds 11 12 13
"""
from __future__ import annotations

import argparse
import json
import sys

import batch
import run
import served
import whatif


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--seeds", nargs="+", required=True)
    args, rest = ap.parse_known_args(argv)
    for seed in args.seeds:
        done = run.execute(["--workload", args.workload, "--seed", seed,
                            "--seconds", args.seconds, "--trace", "0"] + rest)
        if done is None:
            return 2
        ctx = done[0]
        if hasattr(ctx, "batch"):
            control = batch.check(ctx.batch, ctx.state, ctx.fleet, ctx.config,
                                  control=True)
        elif hasattr(ctx, "served"):
            control = served.check(ctx.served, ctx.state, ctx.fleet, ctx.config,
                                   control=True)
        else:
            low = whatif.replay(ctx.trace_obj, ctx.fleet, ctx.config, "bfloat16")
            want = whatif.replay(ctx.trace_obj, ctx.fleet, ctx.config)
            control = whatif.compare(low, want, ctx.trace_obj)
        print(json.dumps({"workload": args.workload, "seed": int(seed),
                          "program": ctx.checks, "control": control,
                          "end_to_end": dict(ctx.e2e, setup_s=ctx.setup_s),
                          "attempted": ctx.attempted, "failed": ctx.failed,
                          "memory_peak_bytes": ctx.memory_peak,
                          "notes": ctx.note}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
