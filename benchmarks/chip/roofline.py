"""Peaks of the chips the benchmark runs on, and the operations and bytes
of the stage-1 screen kernel (``sched_screen``) as functions of its shapes
alone.

Peaks: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16,
819 GB/s HBM).  A device kind missing from the table is an error.
"""
from __future__ import annotations

from typing import Dict, Tuple

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add them "
                       f"to roofline.PEAKS with their source")
    return PEAKS[device_kind]


def batcher_pairs(n: int) -> int:
    """Compare-exchanges of Batcher's odd-even merge sort of n lanes."""
    count, p = 0, 1
    while p < n:
        k = p
        while k >= 1:
            for j in range(k % p, n - k, 2 * k):
                for i in range(min(k, n - j - k)):
                    if (i + j) // (2 * p) == (i + j + k) // (2 * p):
                        count += 1
            k //= 2
        p *= 2
    return count


def screen_counts(n: int, k: int, d: int, m: int) -> Tuple[float, float]:
    """(operations, bytes) one stage-1 screen of ``n`` hosts needs at the
    least: every host's columns read once, the (m+1)-entry shortlist and
    the 10 normalization scalars written.

    Bytes per host: free_f and free_n (2·d f32), schedulable (1 B), domain
    (i32), slow (f32), slot sizes (k·d f32), slot costs (k f32), slot
    validity (k B).  Operations per host: the fit test (d compares each on
    two views), the per-dimension descending sort of slot sizes and the
    ascending sort of slot costs (2 operations per compare-exchange), the
    prefix sums and their compares (2·k per dimension and for the costs),
    and the score (a dozen)."""
    per_host_bytes = 2 * d * 4 + 1 + 4 + 4 + k * d * 4 + k * 4 + k
    out_bytes = 2 * (m + 1) * 4 + 10 * 4
    sorts = (d + 1) * 2 * batcher_pairs(k)
    per_host_ops = 2 * d + sorts + (d + 1) * 2 * k + 12
    return float(n * per_host_ops), float(n * per_host_bytes + out_bytes)


def roofline_share(ops: float, nbytes: float, seconds: float,
                   device_kind: str) -> Tuple[float, str]:
    """Least time the chip could take for ``ops`` and ``nbytes`` over the
    measured ``seconds``, in percent, and which bound sets it."""
    pk = peaks(device_kind)
    t_mem = nbytes / pk["hbm_bytes_per_s"]
    t_ops = ops / pk["flops_per_s"]
    bound = "memory" if t_mem >= t_ops else "compute"
    return 100.0 * max(t_mem, t_ops) / seconds, bound
