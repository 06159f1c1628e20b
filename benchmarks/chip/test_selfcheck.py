"""Self-check of the benchmark's own arithmetic, on the CPU: the reduction
from trace events to busy time, idle gaps and program time, and the
stage-1 screen's byte and operation count against a hand count.  Where a
recorded chip trace is kept beside this file (``selfcheck/``), its
reduction is checked too.

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/test_selfcheck.py
"""
from __future__ import annotations

import glob
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import roofline  # noqa: E402
import tracing  # noqa: E402
from tracing import Event  # noqa: E402

DEV = "/device:TPU:0"


def _events():
    return [
        Event(DEV, "XLA Ops", "fusion.1", 0.0, 1.0),
        Event(DEV, "XLA Ops", "_sched_screen_padded.8", 0.5, 1.5),
        Event(DEV, "XLA Ops", "fusion.2", 3.0, 1.0),
        Event(DEV, "XLA Modules", "jit__drain_entry(7)", 0.0, 2.0),
        Event(DEV, "XLA Modules", "jit__drain_entry(7)", 3.0, 1.0),
        Event("/host:CPU", "python", "bench.wait", 1.8, 1.4),
        Event("/host:CPU", "python", "bench.absorb", 4.2, 0.3),
        Event("/host:CPU", "python", "other.span", 4.0, 1.0),
    ]


def test_union_and_busy():
    assert tracing.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    # fed out of start order, the running union still merges every interval
    busy = tracing._Busy()
    for a, b in [(3, 4), (0, 1), (0.5, 2), (2, 2.5), (3.5, 3.8)]:
        busy.add(a, b)
    assert busy.intervals() == [(0, 2.5), (3, 4)]
    assert tracing.Summary.of(_events(), 5.0).busy == pytest.approx(3.0)
    assert tracing.Summary.of(_events()[5:], 5.0).busy is None


def test_idle_gaps_labelled_by_host_span():
    gaps = dict(tracing.Summary.of(_events(), 5.0).idle_gaps((0.0, 5.0)))
    # idle: [2, 3] while the host waited, [4, 5] with its midpoint in absorb
    assert gaps == {"bench.wait": pytest.approx(1.0), "bench.absorb": pytest.approx(1.0)}


def test_program_and_kernel_time():
    s = tracing.Summary.of(_events(), 5.0)
    assert s.module_time(["_drain_entry"]) == (pytest.approx(3.0), 2)
    assert s.op_time(["sched_screen"]) == (pytest.approx(1.5), 1)
    assert s.span_time(["bench.wait", "bench.absorb"]) == pytest.approx(1.7)
    assert s.top_ops()[0] == ("_sched_screen_padded.8", pytest.approx(1.5))
    assert tracing.short("%while.17 = (s32[]) while(...)") == "while.17"


def test_screen_counts_by_hand():
    # N=2 hosts, K=2 slots, D=1 dimension, M=1:
    # per host 2 f32 views (8 B) + schedulable (1) + domain (4) + slow (4)
    # + slot sizes (8) + slot costs (8) + validity (2) = 35 B; out 2 x 2 x 4
    # + 10 x 4 = 56 B.  Ops per host: fit 2, sorts (1+1) x 2 x 1 pair = 4,
    # prefixes (1+1) x 2 x 2 = 8, score 12 = 26.
    ops, nbytes = roofline.screen_counts(2, 2, 1, 1)
    assert nbytes == 2 * 35 + 56
    assert ops == 2 * 26
    assert [roofline.batcher_pairs(k) for k in (1, 2, 4, 8)] == [0, 1, 5, 19]


def test_roofline_share_and_peaks():
    share, bound = roofline.roofline_share(0.0, 819e9 * 1e-3, 2e-3, "TPU v5 lite")
    assert share == pytest.approx(50.0) and bound == "memory"
    with pytest.raises(KeyError):
        roofline.peaks("an unknown chip")


@pytest.mark.parametrize(
    "path", sorted(glob.glob(os.path.join(HERE, "selfcheck", "*.xplane.pb.gz"))))
def test_recorded_chip_trace(path, tmp_path):
    """A trace recorded on a v5e by ``record_trace.py``: one drain of 64
    decision rows (no fallback at that size), so one drain module and 64
    screen kernel calls; the device was busy for part of the window only,
    inside the drain module."""
    import gzip
    import json
    import shutil

    with gzip.open(path, "rb") as src, open(tmp_path / "t.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    with open(path.replace(".xplane.pb.gz", ".expect.json")) as f:
        want = json.load(f)
    s = tracing.summarize(str(tmp_path), 1.0)
    t_mod, n_mod = s.module_time(["_drain_entry"])
    assert n_mod == want["drain_modules"]
    assert s.op_time(["sched_screen"])[1] == want["screen_calls"]
    assert 0 < s.op_time(["sched_screen"])[0] < t_mod
    assert 0 < s.busy <= s.window_s


def test_every_seed_asks_for_the_same_work():
    """Two seeds hold the same hosts and send the same requests at the same
    pace, in another order: within each block of ``ORDER_BLOCK`` requests
    the gaps and the requests are the same sets."""
    import json

    import numpy as np

    import fleetgen

    with open(os.path.join(HERE, "configs", "t1-12k5.json")) as f:
        cfg = dict(json.load(f), hosts=64)
    a, b = fleetgen.prefill(cfg, 3), fleetgen.prefill(cfg, 2**31 + 9)
    assert not np.array_equal(a.host, b.host)
    assert sorted(np.bincount(a.host, minlength=64)) == sorted(np.bincount(b.host, minlength=64))
    assert a.free_vcpus() == b.free_vcpus()
    n, blk = 3 * fleetgen.ORDER_BLOCK + 17, fleetgen.ORDER_BLOCK
    x = fleetgen.arrivals(cfg, 0.3, n, 3)
    y = fleetgen.arrivals(cfg, 0.3, n, 2**31 + 9)
    assert not np.array_equal(x.life, y.life)
    for lo in range(0, n, blk):
        sl = slice(lo, lo + blk)
        rx = sorted(zip(x.flavor[sl], x.pre[sl], x.life[sl]))
        ry = sorted(zip(y.flavor[sl], y.pre[sl], y.life[sl]))
        assert rx == ry
        end = min(n, lo + blk) - 1
        assert abs(x.t[end] - y.t[end]) <= 1.0
