"""Self-check of ``program_trace``: scope sums over leaf operations only,
the attribution of idle device time to the innermost ``sched.*`` span, and
the same reduction of a drain recorded on a v5e with the program's scopes
and spans (``selfcheck/scoped64.*``, written by ``record_trace.py``).

    JAX_PLATFORMS=cpu python3 -m pytest -q \
        benchmarks/chip/test_program_trace.py
"""
from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import program_trace  # noqa: E402
import tracing  # noqa: E402
from program_trace import Op, ProgramTrace  # noqa: E402

SCOPED = os.path.join(HERE, "selfcheck", "scoped64.xplane.pb.gz")


def _trace():
    ops = [
        # a loop whose event spans its body: not a leaf
        Op(0.0, 6.0, ("jit(run)", "drain", "decide", "while"), leaf=False),
        Op(0.0, 2.0, ("jit(run)", "drain", "decide", "while", "body",
                      "stage1", "pallas_call")),
        Op(2.0, 1.0, ("jit(run)", "drain", "decide", "while", "body",
                      "stage2", "reduce_max")),
        Op(3.0, 0.5, ("jit(run)", "drain", "decide", "while", "body",
                      "transition", "stage2_lookalike")),
        Op(3.5, 0.5, ()),                      # a copy with no op_name
        Op(8.0, 1.0, ("jit(run)", "drain", "queue_pop", "scatter")),
    ]
    spans = [
        (6.0, 10.0, "sched.fetch"),
        (6.5, 7.5, "sched.mirror"),            # nested: innermost wins
        (9.5, 12.0, "sched.depart"),
    ]
    return ProgramTrace.of(ops, spans, 13.0)


def test_scope_sums_count_leaves_only():
    pt = _trace()
    assert pt.scope_s == pytest.approx({
        "drain": 4.5, "decide": 3.5, "stage1": 2.0, "stage2": 1.0,
        "transition": 0.5, "queue_pop": 1.0})
    assert pt.leaf_s == pytest.approx(5.0)      # the loop's 6 s left out
    assert pt.unscoped_s == pytest.approx(0.5)
    assert pt.busy == [(0.0, 6.0), (8.0, 9.0)]


def test_idle_goes_to_the_innermost_span():
    # idle [6, 8]: midpoint 7 in mirror (inside fetch); [9, 13]: midpoint
    # 11 in depart
    assert _trace().idle_by_span() == pytest.approx(
        {"sched.mirror": 2.0, "sched.depart": 4.0})
    pt = _trace()
    pt.spans = [(6.0, 10.0, "sched.fetch")]
    assert pt.idle_by_span() == pytest.approx(
        {"sched.fetch": 2.0, "other": 4.0})


def _ctx(pt, **kw):
    served = types.SimpleNamespace(stats=types.SimpleNamespace(
        attempts=96, drains=3), drains=[], refusals=[])
    base = dict(summary=object(), program=pt, note=[], traced_decisions=4,
                config={"policy": {"admit_batch": 64}}, served=served)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_readers_on_hand_made_trace(monkeypatch):
    pt = _trace()
    monkeypatch.setattr(program_trace, "traced_drains", lambda ctx: 2)
    ctx = _ctx(pt)
    assert program_trace.stage2_device_ms(ctx) == pytest.approx(500.0)
    assert program_trace.idle_program_ms(ctx) == pytest.approx(1.5e3)
    assert program_trace.drain_fill(ctx) == pytest.approx(50.0)


def test_readers_return_none_on_a_program_without_them():
    """A program older than the spans, scopes and counters: every new
    reader reads nothing and raises nothing."""
    pt = ProgramTrace.of([Op(0.0, 1.0, ("jit(run)", "while"))], [], 2.0)
    old = types.SimpleNamespace(stats=types.SimpleNamespace(drains=3),
                                drains=[], refusals=[])
    ctx = _ctx(pt, served=old)
    for reader in (program_trace.stage2_device_ms,
                   program_trace.idle_program_ms, program_trace.drain_fill,
                   program_trace.queue_wait_ms, program_trace.retry_wait_ms):
        assert reader(ctx) is None
    whatif = _ctx(pt, served=None, out={"admission": {"drains": 5}})
    assert program_trace.scan_fallback_share(whatif) is None
    assert program_trace.drain_fill(whatif) is None


def test_window_samples_follow_the_drain_log():
    """One sample per decision, in drain order: the harness's log names
    each sample's request, and only requests decided inside the traced
    window count."""
    stats = types.SimpleNamespace(
        attempts=5, drains=2,
        queue_wall_s=[0.1, 0.2, 0.3, 0.4], retry_wall_s=[0.0, 0.0, 0.5, 0.0],
        fetch_wall_s=[0.01] * 4, tries=[1, 1, 2, 1],
        refused=[False, True, False, False])
    # drain 0: request 0 placed, 1 retried, 2 refused; drain 1: 1 placed,
    # 7 placed (absorbed after the traced window closed)
    served = types.SimpleNamespace(
        stats=stats,
        drains=[[(0, True), (1, False), (2, False)], [(1, True), (7, True)]],
        refusals=[{2}, set()],
        decided_at=np.array([1.0, 1.5, 1.0, 0, 0, 0, 0, 9.0]))
    ctx = _ctx(None, served=served,
               prof=types.SimpleNamespace(wall=(0.5, 2.0)))
    w = program_trace.window_samples(ctx)
    assert list(w["queue_wall_s"]) == [0.1, 0.2, 0.3]
    assert list(w["refused"]) == [False, True, False]
    assert program_trace.queue_wait_ms(ctx) == pytest.approx(300.0)
    assert program_trace.retry_wait_ms(ctx) == pytest.approx(500.0)
    served.refusals = [set(), set()]          # the log and samples disagree
    assert program_trace.window_samples(ctx) is None


def test_recorded_scoped_drain(tmp_path):
    """One drain of 64 requests recorded on a v5e: every stage scope reads
    time (no fallback at that size), the scopes nest, the stage-1 kernel is
    inside ``stage1``, leaves sum to no more than the busy time, and each
    host step has its span."""
    with gzip.open(SCOPED, "rb") as src, \
            open(tmp_path / "t.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    with open(SCOPED.replace(".xplane.pb.gz", ".expect.json")) as f:
        want = json.load(f)
    pt = program_trace.read(str(tmp_path / "t.xplane.pb"))
    s = tracing.summarize(str(tmp_path), 1.0)
    sc = pt.scope_s
    for name in ("queue_push", "queue_select", "decide", "stage1", "stage2",
                 "transition", "queue_pop"):
        assert sc.get(name, 0.0) > 0, name
    assert "fallback" not in sc
    assert sc["decide"] >= sc["stage1"] + sc["stage2"] + sc["transition"]
    assert sc["stage1"] >= s.op_time(["sched_screen"])[0]
    module_s, n_mod = s.module_time(["_drain_entry"])
    assert n_mod == want["drain_modules"]
    assert pt.leaf_s <= sum(b - a for a, b in pt.busy) + 1e-9
    assert sum(sc.get(k, 0.0) for k in ("queue_push", "queue_select",
                                        "decide", "queue_pop")) <= module_s
    names = [n for _, _, n in pt.spans]
    assert names.count("sched.submit") == want["decisions"]
    for name in ("sched.pack", "sched.dispatch", "sched.fetch",
                 "sched.mirror"):
        assert names.count(name) == 1, name
    idle = pt.idle_by_span()
    assert set(idle) <= {"other"} | set(names)
    assert sum(idle.values()) == pytest.approx(
        pt.window_s - sum(min(b, pt.t_lo + pt.window_s) - a
                          for a, b in pt.busy if a < pt.t_lo + pt.window_s),
        rel=1e-6)
