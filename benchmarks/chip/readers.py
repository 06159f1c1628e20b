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


# ---------------------------------------------------------------------------
# the batch cell (``schedule_many`` over a fleet sharded across the chips):
# device times are the mean over the chips, with their spread in a note
# ---------------------------------------------------------------------------

#: ``schedule_many``'s device module
MANY_MODULE = ("_many_entry",)
#: operations (names and HLO categories) that exchange data between chips
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute", "all-to-all",
               "reduce-scatter")
#: the batch harness's host spans outside the call into the program
BATCH_SPANS = ("bench.pack", "bench.absorb", "bench.depart")


def _chip_note(ctx, what: str, patterns) -> float:
    """Mean device seconds over the chips of ``patterns``, noting each
    chip's and the spread between them."""
    per = ctx.summary.per_chip(patterns)
    mean = sum(per) / len(per) if per else 0.0
    if per and mean > 0:
        ctx.note.append(
            f"{what} per chip (s): {', '.join(f'{t:.6f}' for t in per)}; "
            f"spread (max - min) / mean {100.0 * (max(per) - min(per)) / mean:.3f}%")
    return mean


def many_device_us(ctx):
    if ctx.summary is None or not ctx.traced_decisions:
        return None
    t = _chip_note(ctx, "schedule_many module", MANY_MODULE)
    if t <= 0:
        return None
    ctx.note.append(f"many_device_us base: {ctx.traced_decisions} decisions in "
                    f"{ctx.traced_rows} scan rows")
    return 1e6 * t / ctx.traced_decisions


def sharded_screen_roofline(ctx):
    """The stage-1 screen of one shard against ``roofline.screen_counts`` of
    the hosts a chip holds (``ctx.shape``), once per scan row: its time is
    the leaf operations under the program's ``stage1`` scope inside its
    ``shard_map`` (the kernel or the XLA screen, whichever runs), less the
    collectives, averaged over the chips."""
    import program_trace

    pt = program_trace.program(ctx)
    if pt is None or not ctx.traced_rows:
        return None
    per = pt.chip_leaf_s(("stage1", "shard_map"), COLLECTIVES)
    t = sum(per) / len(per) if per else 0.0
    if t <= 0:
        return None
    ctx.note.append("stage-1 screen in each shard per chip (s): " + ", ".join(
        f"{x:.6f}" for x in per) + f"; spread (max - min) / mean "
        f"{100.0 * (max(per) - min(per)) / t:.3f}%")
    ops, nbytes = roofline.screen_counts(*ctx.shape)
    share, bound = roofline.roofline_share(ops * ctx.traced_rows,
                                           nbytes * ctx.traced_rows, t,
                                           ctx.device_kind)
    ctx.note.append(f"screen_roofline: {ctx.traced_rows} screens a chip, "
                    f"{t * 1e6 / ctx.traced_rows:.3f} us each, {nbytes:.0f} B "
                    f"and {ops:.0f} ops each, {bound}-bound")
    return share


def collective_us(ctx):
    if ctx.summary is None or not ctx.traced_decisions:
        return None
    if not ctx.summary.op_time(COLLECTIVES)[1]:
        return None
    t = _chip_note(ctx, "collectives", COLLECTIVES)
    return 1e6 * t / ctx.traced_decisions


def batch_host_ms(ctx):
    """Host ms per decision: the harness's spans around a job (packing its
    requests, absorbing its outcomes, departures) and the device-idle time
    inside its call into the program (the program's own packing, dispatch,
    fetch and absorption)."""
    if ctx.summary is None or not ctx.traced_decisions:
        return None
    inside = dict(ctx.summary.idle_gaps()).get("bench.schedule", 0.0)
    return 1e3 * (ctx.summary.span_time(BATCH_SPANS) + inside) / ctx.traced_decisions
