"""Shared benchmark fixtures: the paper's testbed geometry, fleet builders,
timing helpers, and the machine-readable results sink.

Every ``emit()`` row is printed as the historical ``name,us,derived`` CSV AND
recorded in-process; each benchmark module flushes its rows to
``$REPRO_BENCH_OUT/BENCH_<module>.json`` (default ``bench_out/``) with
per-config mean/p50 latency, so CI can archive results as artifacts and
regressions are diffable without parsing stdout.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, List, NamedTuple, Optional

import numpy as np

from repro.core.types import VM_SPEC, Host, Instance

#: CI smoke mode: shrink every fleet/duration so ``python -m benchmarks.run``
#: exercises all entrypoints in seconds rather than minutes.
TINY = os.environ.get("REPRO_BENCH_TINY", "") not in ("", "0")

#: Where BENCH_*.json files land (created on demand).
OUT_DIR = os.environ.get("REPRO_BENCH_OUT", "bench_out")

#: Persistent compile cache of the entry-point scripts when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed path inside the checkout
#: (git-ignored), because the path is part of the cache key.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads it and
    nothing else is configured; otherwise the cache goes to
    ``COMPILE_CACHE_DIR``.  Returns the directory in use.  Call it before
    the first compile; library modules and tests never call it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax

        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path

SIZES = {
    "small": VM_SPEC.make(vcpus=1, ram_mb=2000, disk_gb=20),
    "medium": VM_SPEC.make(vcpus=2, ram_mb=4000, disk_gb=40),
    "large": VM_SPEC.make(vcpus=4, ram_mb=8000, disk_gb=80),
}
#: paper Table 1 nodes (disk non-binding; see tests/test_scheduler_correctness)
NODE_CAP = VM_SPEC.make(vcpus=8, ram_mb=16000, disk_gb=10_000)
#: double-size nodes for the K>8 oversubscription sweep (up to 16 small slots)
BIG_NODE_CAP = VM_SPEC.make(vcpus=16, ram_mb=32000, disk_gb=10_000)
NOW = 1_000_000.0


def empty_fleet(n: int) -> List[Host]:
    return [Host(name=f"h{i}", capacity=NODE_CAP) for i in range(n)]


def saturated_fleet(n: int, seed: int = 0, preemptible_frac: float = 0.5,
                    k_max: int = 4) -> List[Host]:
    """Hosts filled with medium instances, mixed normal/preemptible, integer
    run-time minutes (paper §4.4.1 conditions)."""
    rng = np.random.default_rng(seed)
    hosts = []
    iid = 0
    for i in range(n):
        h = Host(name=f"h{i}", capacity=NODE_CAP)
        n_pre = 0
        for _ in range(4):  # 4 medium slots per node
            pre = bool(rng.random() < preemptible_frac) and n_pre < k_max
            n_pre += int(pre)
            h.place(Instance(
                id=f"x{iid}", resources=SIZES["medium"], preemptible=pre,
                host=h.name, start_time=NOW - float(rng.integers(10, 500)) * 60.0,
            ))
            iid += 1
        if n_pre == 0:  # guarantee evacuability somewhere
            inst = next(iter(h.instances.values()))
            inst.preemptible = True
        hosts.append(h)
    return hosts


class Timing(NamedTuple):
    mean_us: float
    std_us: float
    p50_us: float


def time_call(fn: Callable, repeats: int = 30, warmup: int = 3) -> Timing:
    """Mean/std/median latency of fn() in microseconds."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e6)
    return Timing(float(np.mean(ts)), float(np.std(ts)), float(np.median(ts)))


#: rows emitted since the last ``write_bench_json`` flush
_RECORDS: List[dict] = []


def emit(name: str, us: float, derived: str, p50_us: Optional[float] = None) -> None:
    """Print the historical CSV row and record it for the JSON sink."""
    print(f"{name},{us:.1f},{derived}")
    row = {"name": name, "mean_us": round(float(us), 3), "derived": derived}
    if p50_us is not None:
        row["p50_us"] = round(float(p50_us), 3)
    _RECORDS.append(row)


def write_bench_json(module: str) -> Optional[str]:
    """Flush rows recorded since the previous call to BENCH_<module>.json.

    Returns the path written (None when nothing was recorded — e.g. the
    roofline table with no dry-run artifacts present)."""
    global _RECORDS
    rows, _RECORDS = _RECORDS, []
    if not rows:
        return None
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"BENCH_{module}.json")
    with open(path, "w") as f:
        json.dump({"module": module, "tiny": TINY, "rows": rows}, f, indent=1)
    return path
