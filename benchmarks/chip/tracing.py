"""Reduction of a JAX profiler trace to the quantities the per-layer
metrics read: device busy time (the union of the intervals in which an
operation ran), the device time of named programs and kernels, the
harness's host spans, and the idle gaps labelled by the span the host was
in.  Times are seconds.

A trace streams through ``Summary.add`` one event at a time (a traced
scan dispatch holds millions of operations), so the same arithmetic runs
on a recorded chip trace and on the small hand-made event lists of
``test_selfcheck``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: lines of a TPU device plane holding the operations that ran, and the
#: programs (the step lines repeat the same time at a coarser grain)
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
#: the harness's own host spans
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float     # seconds
    dur: float       # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur


def short(name: str) -> str:
    """An HLO operation's event name without its operands (``%fusion.3 =
    f32[...] fusion(...)`` -> ``fusion.3``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_device(plane: str) -> bool:
    return plane.startswith("/device:TPU:") and not plane.endswith("SparseCore")


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class _Busy:
    """Union of intervals fed mostly in start order (others kept aside)."""

    def __init__(self):
        self.merged: List[List[float]] = []
        self.stray: List[Tuple[float, float]] = []

    def add(self, a: float, b: float) -> None:
        m = self.merged
        if not m or a >= m[-1][0]:
            if m and a <= m[-1][1]:
                m[-1][1] = max(m[-1][1], b)
            else:
                m.append([a, b])
        else:
            self.stray.append((a, b))

    def intervals(self) -> List[Tuple[float, float]]:
        return union([tuple(x) for x in self.merged] + self.stray)


class Summary:
    """What the per-layer readers get from one traced window."""

    def __init__(self, window_s: float = 0.0):
        self.window_s = window_s
        self._busy: Dict[str, _Busy] = defaultdict(_Busy)
        self.ops: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
        self.modules: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
        self.spans: List[Event] = []
        self.t_lo, self.t_hi = float("inf"), float("-inf")

    def add(self, e: Event) -> None:
        if is_device(e.plane):
            self.t_lo, self.t_hi = min(self.t_lo, e.start), max(self.t_hi, e.end)
            if e.line in OP_LINES:
                self._busy[e.plane].add(e.start, e.end)
                acc = self.ops[e.name]
            elif e.line in MODULE_LINES:
                acc = self.modules[e.name]
            else:
                return
            acc[0] += e.dur
            acc[1] += 1
        elif e.plane.startswith("/host:") and e.name.startswith(SPAN_PREFIX):
            self.spans.append(e)

    @classmethod
    def of(cls, events: Iterable[Event], window_s: float) -> "Summary":
        s = cls(window_s)
        for e in events:
            s.add(e)
        return s

    @property
    def busy(self) -> Optional[float]:
        """Busy seconds averaged over the device planes (None: no device
        operation in the trace)."""
        per = [sum(b - a for a, b in v.intervals()) for v in self._busy.values()]
        return sum(per) / len(per) if per else None

    @staticmethod
    def _sum(table, patterns: Sequence[str]) -> Tuple[float, int]:
        t, n = 0.0, 0
        for name, (dt, c) in table.items():
            if any(p in name for p in patterns):
                t, n = t + dt, n + c
        return t, n

    def module_time(self, patterns: Sequence[str]) -> Tuple[float, int]:
        return self._sum(self.modules, patterns)

    def op_time(self, patterns: Sequence[str]) -> Tuple[float, int]:
        return self._sum(self.ops, patterns)

    def span_time(self, names: Sequence[str]) -> float:
        return sum(e.dur for e in self.spans if e.name in names)

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Device operations that took most time, summed by name (a
        ``while`` operation's time holds that of the operations in its
        body)."""
        return sorted(((k, v[0]) for k, v in self.ops.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, window: Optional[Tuple[float, float]] = None
                  ) -> List[Tuple[str, float]]:
        """Idle device time in ``window`` (default: from the first device
        event over ``window_s``) summed by the harness span the host was in
        at each gap's midpoint ("other" outside every span), longest
        first."""
        if not self._busy:
            return []
        if window is None:
            window = (self.t_lo, self.t_lo + self.window_s)
        busy = union(iv for v in self._busy.values() for iv in v.intervals())
        spans = sorted((e.start, e.end, e.name) for e in self.spans)
        gaps, t = [], window[0]
        for a, b in busy:
            if a > t:
                gaps.append((t, min(a, window[1])))
            t = max(t, b)
        if t < window[1]:
            gaps.append((t, window[1]))
        total: Dict[str, float] = defaultdict(float)
        for a, b in gaps:
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            label = "other"
            for s0, s1, name in spans:
                if s0 <= mid <= s1:
                    label = name
                    break
                if s0 > mid:
                    break
            total[label] += b - a
        return sorted(total.items(), key=lambda kv: -kv[1])


def summarize(trace_dir: str, host_window_s: float) -> Summary:
    """Stream the newest ``.xplane.pb`` under ``trace_dir`` into a
    ``Summary``; the window is the profiled one where the trace records
    it, else ``host_window_s``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    s = Summary(host_window_s)
    for plane in data.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            a, b = st.get("profile_start_time"), st.get("profile_stop_time")
            if a is not None and b is not None:
                s.window_s = (float(b) - float(a)) * 1e-9
            continue
        device = is_device(plane.name)
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name not in OP_LINES + MODULE_LINES:
                continue
            for ev in line.events:
                name = ev.name
                if device:
                    name = short(name)
                elif not name.startswith(SPAN_PREFIX):
                    continue
                s.add(Event(plane.name, line.name, name,
                            ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return s
