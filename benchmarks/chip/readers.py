"""Shared arithmetic of the per-layer readers (one file per metric under
``metrics/`` picks one of these).  A reader gets the run's context and
returns the metric's value, or None when its window holds nothing to read.

Context fields: ``summary`` (``tracing.Summary`` of the traced window, or
None), ``traced_decisions`` and ``traced_drains`` (decisions absorbed and
drains dispatched inside the traced window), ``shortlist`` (the program's
``shortlist_stats`` after the window), ``compiles`` (programs traced or
compiled inside the window), ``traced_events`` (trace events replayed by
the traced scan dispatches), ``shape`` (N, K, D, M of the screen),
``device_kind``, and ``note`` (a list of lines printed before the result).
"""
from __future__ import annotations

import roofline

#: host spans of the harness's calls into the front end (device waits apart)
FRONTEND_SPANS = ("bench.submit", "bench.drain", "bench.absorb", "bench.depart")
#: the drain program's device module
DRAIN_MODULE = ("_drain_entry",)
#: the scanned simulator's device module
SCAN_MODULE = ("jit(run)", "jit_run")
#: the stage-1 screen kernel's device operation
SCREEN_KERNEL = ("sched_screen",)


def frontend_host_ms(ctx):
    if ctx.summary is None or not ctx.traced_decisions:
        return None
    return 1e3 * ctx.summary.span_time(FRONTEND_SPANS) / ctx.traced_decisions


def drain_device_ms(ctx):
    if ctx.summary is None:
        return None
    t, n = ctx.summary.module_time(DRAIN_MODULE)
    return 1e3 * t / n if n else None


def fallback_share(ctx):
    st = ctx.shortlist
    if not st or not st.get("decisions"):
        return None
    ctx.note.append(f"fallback_share base: {st['fallbacks']} fallbacks in "
                    f"{st['decisions']} decisions at M={st['shortlist']}")
    return 100.0 * st["fallbacks"] / st["decisions"]


def screen_roofline(ctx):
    if ctx.summary is None:
        return None
    t, calls = ctx.summary.op_time(SCREEN_KERNEL)
    if not calls or t <= 0:
        return None
    ops, nbytes = roofline.screen_counts(*ctx.shape)
    share, bound = roofline.roofline_share(ops * calls, nbytes * calls, t,
                                           ctx.device_kind)
    ctx.note.append(f"screen_roofline: {calls} kernel calls, {t * 1e6 / calls:.3f} "
                    f"us each, {nbytes:.0f} B and {ops:.0f} ops per call, "
                    f"{bound}-bound")
    return share


def scan_device_us_per_event(ctx):
    if ctx.summary is None or not ctx.traced_events:
        return None
    t, n = ctx.summary.module_time(SCAN_MODULE)
    return 1e6 * t / ctx.traced_events if n else None


def device_idle(ctx):
    if ctx.summary is None or not ctx.summary.busy or ctx.summary.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.summary.busy / ctx.summary.window_s)


def compiles_in_window(ctx):
    return None if ctx.compiles is None else float(ctx.compiles)
