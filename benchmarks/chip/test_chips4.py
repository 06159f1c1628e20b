"""Self-check of the four-chip readers on a trace recorded on four v5e
chips by ``record_trace.py --chips 4`` (``selfcheck/chips4/``): one
``schedule_batch`` job of 64 requests on a 4 096-host sharded-1m fleet.
Device times are the mean over the chips' planes, the counts are the sum,
and the readers give the readings kept beside the trace (those the chip
gave; the screen's and stage 2's were added later, computed from this
trace on the CPU).  The screen's time is pinned apart from its reader:
its operations are counted from the trace's own events.

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/test_chips4.py
"""
from __future__ import annotations

import collections
import gzip
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import program_trace  # noqa: E402
import readers  # noqa: E402
import record_trace  # noqa: E402
import tracing  # noqa: E402
from tracing import Event  # noqa: E402

FIXTURE = os.path.join(HERE, "selfcheck", "chips4", "batch4.xplane.pb.gz")


def test_device_time_is_the_mean_over_chips():
    ev = [Event(f"/device:TPU:{c}", "XLA Ops", "all-gather.1", 0.0, 1.0 + c)
          for c in range(4)]
    ev += [Event("/device:TPU:0", "XLA Modules", "jit__many_entry(1)", 0.0, 4.0)]
    s = tracing.Summary.of(ev, 8.0)
    assert s.chips == 4
    assert s.busy == pytest.approx(2.5)
    assert s.per_chip(readers.COLLECTIVES) == pytest.approx([1.0, 2.0, 3.0, 4.0])
    assert s.top_ops() == [("all-gather.1", pytest.approx(2.5))]
    assert s.op_time(readers.COLLECTIVES) == (pytest.approx(10.0), 4)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("chips4")
    with gzip.open(FIXTURE, "rb") as src, open(tmp / "t.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    with open(FIXTURE.replace(".xplane.pb.gz", ".expect.json")) as f:
        want = json.load(f)
    return tracing.summarize(str(tmp), 1.0), want, str(tmp)


def test_recorded_counts(recorded):
    s, want, _ = recorded
    assert s.chips == want["chips"] == 4
    assert s.module_time(readers.MANY_MODULE)[1] == want["many_modules"]
    assert s.module_time(readers.MANY_MODULE)[1] == 4 and want["rows"] == 64
    assert s.op_time(readers.COLLECTIVES)[1] > 0
    assert want["all_gather_in_program"]
    per = s.per_chip(readers.MANY_MODULE)
    assert len(per) == 4 and min(per) > 0
    assert 0 < s.busy <= s.window_s


def test_recorded_readings(recorded):
    _, want, tmp = recorded
    got, notes = record_trace.batch4_readings(tmp, tuple(want["shape"]),
                                              want["device_kind"])
    assert got == pytest.approx(want["readings"], rel=1e-9)
    assert 0 < got["screen_roofline"] <= 100
    assert all(v > 0 for v in got.values())
    assert any("spread" in n for n in notes)


def test_screen_time_is_its_operations(recorded):
    """On each chip the screen reader's seconds are those of the operations
    under ``stage1`` inside ``shard_map`` (less the two all-reduces of its
    normalisation constants): ``screen_ops`` operations, each run once per
    scan row, counted here from the trace's events."""
    _, want, tmp = recorded
    path = program_trace.newest(tmp)
    with open(path, "rb") as f:
        space = program_trace._xspace_class().FromString(f.read())
    runs, secs = {}, {}
    for plane in space.planes:
        if not tracing.is_device(plane.name):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        ops = {}
        for mid, m in plane.event_metadata.items():
            st = {names.get(x.metadata_id): program_trace._stat_str(x, names)
                  for x in m.stats}
            scopes = st.get("tf_op", "").split(":")[0].split("/")
            if ("stage1" in scopes and "shard_map" in scopes
                    and st.get("hlo_category") != "all-reduce"):
                ops[mid] = m.name
        n, t = collections.Counter(), 0.0
        for line in plane.lines:
            if line.name in tracing.OP_LINES:
                for ev in line.events:
                    if ev.metadata_id in ops:
                        n[ops[ev.metadata_id]] += 1
                        t += ev.duration_ps * 1e-12
        runs[plane.name], secs[plane.name] = n, t
    assert len(runs) == want["chips"]
    for n in runs.values():
        assert len(n) == want["screen_ops"]
        assert set(n.values()) == {want["rows"]}
    got = program_trace.read(path).chip_leaf_s(("stage1", "shard_map"),
                                               readers.COLLECTIVES)
    assert got == pytest.approx([secs[k] for k in sorted(secs)], rel=1e-12)
