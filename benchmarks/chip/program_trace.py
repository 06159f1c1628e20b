"""The program's own layers in a traced window, and the per-layer readers
that read them: its host spans (``sched.*``, written by
``repro.core.obs``), the device time under each of its ``jax.named_scope``
stages, and the counters and per-request wall samples it keeps
(``AdmissionStats`` of the front end, the scan's admission dict).  A
reader returns None where the program has none of these.

Device time per scope sums leaf operations only: the event of a
``while``, ``conditional`` or ``call`` operation spans its body, whose
operations have events of their own.  An operation counts toward every
scope on its ``op_name`` path (``stage1`` inside ``decide`` inside
``drain``); one whose metadata holds no path (a copy the compiler put in)
counts toward none.

``jax.profiler.ProfileData`` gives no event-metadata stats, and each
operation's ``op_name`` is one (``tf_op``), so the trace is read here as
the XPlane protobuf, through a minimal schema of the fields it needs.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import math
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

import readers
import tracing

#: where run.py's profiler writes the traced window
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".cache", "trace")
SPAN_PREFIX = "sched."
#: the program's device scopes, in the order the notes print them
SCOPES = ("queue_push", "queue_select", "decide", "stage1", "stage2",
          "fallback", "transition", "queue_pop", "arrival", "departure",
          "drain", "epilogue", "fail_host", "heal_host", "checkpoint",
          "zone_storm")
#: HLO categories whose event holds its body's operations
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Op:
    """One device operation's event: start and duration (s), the
    components of its ``op_name`` path, and whether it is a leaf."""
    start: float
    dur: float
    path: Tuple[str, ...]
    leaf: bool = True


@dataclasses.dataclass
class ProgramTrace:
    scope_s: Dict[str, float]   # leaf seconds under each scope seen
    leaf_s: float               # every leaf operation's seconds
    unscoped_s: float           # leaf seconds with no op_name path
    busy: List[Tuple[float, float]]       # union of operation intervals
    spans: List[Tuple[float, float, str]]  # sched.* host spans
    t_lo: float                 # first device event
    window_s: float
    #: each device plane's leaf operations: (``op_name`` path, HLO
    #: category, seconds), one entry per operation
    plane_leaves: Dict[str, List[Tuple[Tuple[str, ...], str, float]]] = \
        dataclasses.field(default_factory=dict)

    @classmethod
    def of(cls, ops: Iterable[Op], spans: Iterable[Tuple[float, float, str]],
           window_s: float) -> "ProgramTrace":
        acc = _Acc()
        for op in ops:
            acc.interval(op.start, op.dur)
            if op.leaf:
                acc.leaf(op.dur, op.path)
        return acc.result(list(spans), window_s)

    @property
    def chips(self) -> int:
        """Device planes that ran a leaf operation (at least 1)."""
        return max(1, len(self.plane_leaves))

    def chip_leaf_s(self, need: Sequence[str],
                    skip: Sequence[str] = ()) -> List[float]:
        """Seconds of the leaf operations on each device plane (sorted by
        plane) whose ``op_name`` path holds every scope in ``need``,
        leaving out those whose HLO category holds one of ``skip``."""
        return [sum(d for path, cat, d in self.plane_leaves[p]
                    if all(x in path for x in need)
                    and not any(x in cat for x in skip))
                for p in sorted(self.plane_leaves)]

    def idle_by_span(self) -> Dict[str, float]:
        """Idle device seconds from the first device event over the
        window, by the innermost ``sched.*`` span the host was in at each
        gap's midpoint ("other": in none)."""
        lo, hi = self.t_lo, self.t_lo + self.window_s
        gaps, t = [], lo
        for a, b in self.busy:
            if a > t:
                gaps.append((t, min(a, hi)))
            t = max(t, b)
        if t < hi:
            gaps.append((t, hi))
        spans = sorted(self.spans)
        total: Dict[str, float] = collections.defaultdict(float)
        active: List[Tuple[float, float, str]] = []
        k = 0
        for a, b in gaps:
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            while k < len(spans) and spans[k][0] <= mid:
                active.append(spans[k])
                k += 1
            active = [s for s in active if s[1] >= mid]
            total[active[-1][2] if active else "other"] += b - a
        return dict(total)


class _Acc:
    """Running sums of one pass over a trace's device operations."""

    def __init__(self):
        self.intervals: List[Tuple[float, float]] = []
        self.scope_s: Dict[str, float] = collections.defaultdict(float)
        self.plane_leaves: Dict[str, list] = collections.defaultdict(list)
        self.leaf_s = self.unscoped_s = 0.0

    def interval(self, start: float, dur: float) -> None:
        self.intervals.append((start, start + dur))

    def leaf(self, dur: float, path: Tuple[str, ...], plane: str = "",
             category: str = "") -> None:
        self.leaf_s += dur
        if not path:
            self.unscoped_s += dur
        for name in set(path).intersection(SCOPES):
            self.scope_s[name] += dur
        self.plane_leaves[plane].append((path, category, dur))

    def result(self, spans, window_s: float) -> ProgramTrace:
        t_lo = min((a for a, _ in self.intervals), default=0.0)
        return ProgramTrace(dict(self.scope_s), self.leaf_s, self.unscoped_s,
                            tracing.union(self.intervals), spans, t_lo,
                            window_s, dict(self.plane_leaves))


# ---------------------------------------------------------------------------
# the XPlane protobuf, by a minimal schema
# ---------------------------------------------------------------------------


def _xspace_class():
    """The ``XSpace`` message class of tsl's ``xplane.proto``, holding only
    the fields read here (same field numbers; the rest are skipped)."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    fd = descriptor_pb2.FileDescriptorProto(
        name="benchmarks_xplane_min.proto", package="xmin", syntax="proto3")

    def message(name, fields, parent=None):
        m = (parent.nested_type if parent else fd.message_type).add(name=name)
        for fname, number, ftype, label, tname in fields:
            f = m.field.add(name=fname, number=number, type=ftype, label=label)
            if tname:
                f.type_name = ".xmin." + tname
        return m

    i64, u64 = F.TYPE_INT64, F.TYPE_UINT64
    s, msg = F.TYPE_STRING, F.TYPE_MESSAGE
    message("XStat", [("metadata_id", 1, i64, one, None),
                      ("uint64_value", 3, u64, one, None),
                      ("int64_value", 4, i64, one, None),
                      ("str_value", 5, s, one, None),
                      ("ref_value", 7, u64, one, None)])
    message("XEvent", [("metadata_id", 1, i64, one, None),
                       ("offset_ps", 2, i64, one, None),
                       ("duration_ps", 3, i64, one, None),
                       ("stats", 4, msg, many, "XStat")])
    message("XLine", [("name", 2, s, one, None),
                      ("timestamp_ns", 3, i64, one, None),
                      ("events", 4, msg, many, "XEvent")])
    message("XEventMetadata", [("id", 1, i64, one, None),
                               ("name", 2, s, one, None),
                               ("stats", 5, msg, many, "XStat")])
    message("XStatMetadata", [("id", 1, i64, one, None),
                              ("name", 2, s, one, None)])
    plane = message("XPlane", [
        ("name", 2, s, one, None), ("lines", 3, msg, many, "XLine"),
        ("event_metadata", 4, msg, many, "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, msg, many, "XPlane.StatMetadataEntry"),
        ("stats", 6, msg, many, "XStat")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = message(entry, [("key", 1, i64, one, None),
                            ("value", 2, msg, one, value)], parent=plane)
        e.options.map_entry = True
    message("XSpace", [("planes", 1, msg, many, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("xmin.XSpace"))


def _stat_str(stat, stat_names: Dict[int, str]) -> str:
    """A string stat's value, stored inline or as a reference to a stat
    metadata entry's name."""
    return stat.str_value or stat_names.get(stat.ref_value, "")


def read(path: str) -> ProgramTrace:
    """One pass over the ``.xplane.pb`` at ``path``."""
    with open(path, "rb") as f:
        space = _xspace_class().FromString(f.read())
    acc, spans, window_s = _Acc(), [], 0.0
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        if plane.name == "Task Environment":
            st = {names.get(x.metadata_id): x for x in plane.stats}
            a, b = st.get("profile_start_time"), st.get("profile_stop_time")
            if a is not None and b is not None:
                window_s = ((b.uint64_value or b.int64_value)
                            - (a.uint64_value or a.int64_value)) * 1e-9
        elif tracing.is_device(plane.name):
            _device_plane(plane, names, acc)
        elif plane.name.startswith("/host:"):
            meta = {k: v.name for k, v in plane.event_metadata.items()
                    if v.name.startswith(SPAN_PREFIX)}
            for line in plane.lines:
                base = line.timestamp_ns * 1e-9
                for ev in line.events:
                    name = meta.get(ev.metadata_id)
                    if name is not None:
                        a = base + ev.offset_ps * 1e-12
                        spans.append((a, a + ev.duration_ps * 1e-12, name))
    return acc.result(spans, window_s)


def _device_plane(plane, names: Dict[int, str], acc: _Acc) -> None:
    dur: Dict[int, float] = collections.defaultdict(float)
    for line in plane.lines:
        if line.name not in tracing.OP_LINES:
            continue
        base = line.timestamp_ns * 1e-9
        for ev in line.events:
            d = ev.duration_ps * 1e-12
            acc.interval(base + ev.offset_ps * 1e-12, d)
            dur[ev.metadata_id] += d
    for mid, d in dur.items():
        st = {names.get(x.metadata_id): _stat_str(x, names)
              for x in plane.event_metadata[mid].stats}
        cat = st.get("hlo_category", "")
        if cat in CONTAINERS:
            continue
        tf_op = st.get("tf_op", "")
        acc.leaf(d, tuple(tf_op.rsplit(":", 1)[0].split("/")) if tf_op else (),
                 plane.name, cat)


def newest(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    return paths[-1]


# ---------------------------------------------------------------------------
# readers (one file per metric under metrics/ picks one)
# ---------------------------------------------------------------------------


def program(ctx) -> Optional[ProgramTrace]:
    """The traced window's ``ProgramTrace``, read once per run (None when
    the run was not traced); the first read notes each scope's device ms
    per drain and the idle time by span."""
    if ctx.summary is None:
        return None
    if getattr(ctx, "program", None) is None:
        pt = ctx.program = read(newest(getattr(ctx, "trace_dir", TRACE_DIR)))
        n, unit = per_unit(ctx)
        per = ", ".join(f"{k} {1e3 * pt.scope_s.get(k, 0.0) / pt.chips / n:.3f}"
                        for k in SCOPES if k in pt.scope_s) if n else "-"
        ctx.note.append(
            f"program scopes, device ms per {unit} over {n} {unit}s, mean of "
            f"{pt.chips} chip(s): {per}; "
            f"leaf ops {1e3 * pt.leaf_s:.3f} ms, of which unscoped "
            f"{1e3 * pt.unscoped_s:.3f} ms")
        if pt.busy:
            idle = sorted(pt.idle_by_span().items(), key=lambda kv: -kv[1])
            ctx.note.append("idle by program span (s): " + ", ".join(
                f"{k} {v:.4f}" for k, v in idle))
    return ctx.program


def _stats(ctx):
    """The served front end's ``AdmissionStats`` (None off the served
    path)."""
    served = getattr(ctx, "served", None)
    return None if served is None else served.stats


def _admission(ctx) -> Dict[str, int]:
    """The what-if scan's admission counters (empty off that path)."""
    out = getattr(ctx, "out", None)
    return {} if out is None else out["admission"]


def traced_drains(ctx) -> int:
    """Drains that ran inside the traced window: drain programs in the
    trace (served), or drains per dispatch times traced dispatches."""
    if getattr(ctx, "served", None) is not None:
        return ctx.summary.module_time(readers.DRAIN_MODULE)[1]
    n_real = getattr(getattr(ctx, "trace_obj", None), "n_real", 0)
    if not n_real:
        return 0
    return _admission(ctx).get("drains", 0) * (ctx.traced_events // n_real)


def per_unit(ctx) -> Tuple[int, str]:
    """What a device time is read per: the traced drains, or the traced
    scan rows where the cell has no drains (the batch cell)."""
    n = traced_drains(ctx)
    if not n and getattr(ctx, "traced_rows", 0):
        return ctx.traced_rows, "scan row"
    return n, "drain"


def drain_fill(ctx):
    st = _stats(ctx)
    if st is not None:
        attempts, drains = getattr(st, "attempts", None), st.drains
    else:
        adm = _admission(ctx)
        attempts, drains = adm.get("attempts"), adm.get("drains")
    if attempts is None or not drains:
        return None
    batch = ctx.config["policy"]["admit_batch"]
    ctx.note.append(f"drain_fill base: {attempts} attempts in {drains} "
                    f"drains of {batch} rows")
    return 100.0 * attempts / (drains * batch)


def scan_fallback_share(ctx):
    adm = _admission(ctx)
    if not adm.get("attempts") or "fallbacks" not in adm:
        return None
    ctx.note.append(f"scan fallback_share base: {adm['fallbacks']} "
                    f"fallbacks in {adm['attempts']} attempts")
    return 100.0 * adm["fallbacks"] / adm["attempts"]


def stage2_device_ms(ctx):
    """Device ms under the ``stage2`` scope per drain, or per scan row
    where the cell has no drains, averaged over the chips."""
    pt = program(ctx)
    if pt is None or "stage2" not in pt.scope_s:
        return None
    n = per_unit(ctx)[0]
    return 1e3 * pt.scope_s["stage2"] / pt.chips / n if n else None


def idle_program_ms(ctx):
    pt = program(ctx)
    if pt is None or not (pt.busy and pt.spans and ctx.traced_decisions):
        return None
    idle = pt.idle_by_span()
    return 1e3 * sum(v for k, v in idle.items() if k != "other") \
        / ctx.traced_decisions


def window_samples(ctx) -> Optional[Dict[str, np.ndarray]]:
    """The program's wall samples (s) and tries of the requests whose
    decision, by placement or by refusal after retries, the harness
    absorbed inside the traced window: the profiler's stop at its end holds
    the open loop for seconds, and what follows is that stall's backlog.
    The front end records one sample per such decision in drain order; the
    harness's log of each drain's attempts names the request of each."""
    st = _stats(ctx)
    if st is None or not hasattr(st, "queue_wall_s"):
        return None
    s = ctx.served
    ids = np.asarray([a[0] for attempts, refused in zip(s.drains, s.refusals)
                      for a in attempts if a[1] or a[0] in refused], int)
    if ids.size != len(st.queue_wall_s):
        ctx.note.append(f"wall samples: {len(st.queue_wall_s)} samples for "
                        f"{ids.size} logged decisions; not read")
        return None
    t0, t1 = ctx.prof.wall
    at = s.decided_at[ids] if ids.size else np.zeros(0)
    keep = (at >= t0) & (at <= t1)
    if not keep.any():
        return None
    return {k: np.asarray(getattr(st, k))[keep] for k in (
        "queue_wall_s", "retry_wall_s", "fetch_wall_s", "tries", "refused")}


def p95(v: np.ndarray) -> float:
    """95th percentile by nearest rank."""
    v = np.sort(v)
    return float(v[max(0, math.ceil(0.95 * v.size) - 1)])


def queue_wait_ms(ctx):
    w = window_samples(ctx)
    if w is None:
        return None
    total = w["queue_wall_s"] + w["retry_wall_s"] + w["fetch_wall_s"]
    slow = np.argsort(total)[-max(1, math.ceil(0.05 * total.size)):]
    ctx.note.append(
        f"slowest 5% of {total.size} requests decided in the traced window, "
        f"by submit -> fetched ({slow.size}, from "
        f"{1e3 * total[slow].min():.1f} ms): "
        f"{100.0 * w['refused'][slow].mean():.1f}% refusals, mean tries "
        f"{w['tries'][slow].mean():.2f}, mean queue / retry / fetch "
        f"{1e3 * w['queue_wall_s'][slow].mean():.1f} / "
        f"{1e3 * w['retry_wall_s'][slow].mean():.1f} / "
        f"{1e3 * w['fetch_wall_s'][slow].mean():.1f} ms; all requests: "
        f"{100.0 * w['refused'].mean():.1f}% refusals, mean tries "
        f"{w['tries'].mean():.2f}, fetch p95 "
        f"{1e3 * p95(w['fetch_wall_s']):.1f} ms")
    return 1e3 * p95(w["queue_wall_s"])


def retry_wait_ms(ctx):
    w = window_samples(ctx)
    return None if w is None else 1e3 * p95(w["retry_wall_s"])
