#!/usr/bin/env python3
"""Chip benchmark of the preemptible-instance scheduler: runs one cell of
``BENCHMARK.json`` once and prints one JSON result line.

    python3 benchmarks/chip/run.py --workload t1-12k5.open --seed 7 \\
        --seconds 30 --trace 0

A cell names a configuration (``configs/<name>.json``: the fleet, its
prefill and the scheduler policy) and a traffic mix (``traffic/<name>.json``:
the mode and its rates).  ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` runs the same window under the profiler and reports
its per-layer metrics, each read by ``metrics/<metric>.py``.

Inputs are made from ``--seed``.  The run refuses a machine whose JAX
backend is not a TPU, or has fewer chips than the cell asks for: non-zero
exit and no result line.  ``--rehearse`` (never passed by a check) runs a
tiny fleet on the CPU with the stage-1 kernel in interpret mode, to try
the harness without a chip; ``--chips`` (never passed by a check either)
runs a cell on another number of chips than it asks for, as the one-chip
comparison of a four-chip cell does.  A cell on more than one chip shards
the fleet host-major over a mesh of its chips.

After the window the program's answers are replayed through the plain
reference (``reference.py``); each number compared is printed with its
limit, as the last lines on standard error and under ``checks``, the last
key of the result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import numpy as np  # noqa: E402

import fleetgen  # noqa: E402

#: every number the check compares is an exact count of disagreements
LIMITS = {
    "queue_order": 0, "decisions": 0, "refusals": 0, "departures": 0,
    "counters": 0, "state_cells": 0, "outcomes": 0, "waits": 0, "raised": 0,
}
#: a rehearsal's fleet and wall rate (CPU, interpret mode)
REHEARSE_HOSTS = 640
REHEARSE_WALL_RATE = 40.0
CACHE_DIR = os.path.join(BENCH, ".cache", "jax")
TRACE_DIR = os.path.join(BENCH, ".cache", "trace")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_of(spec, name):
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def start_jax(rehearse: bool, chips: int):
    """Import JAX with the persistent compilation cache in the checkout (or
    where JAX_COMPILATION_CACHE_DIR says), and check the devices."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if not rehearse and devices[0].platform != "tpu":
        log(f"refused: the first device is {devices[0].platform!r}, not a TPU")
        return None
    if len(devices) < chips:
        log(f"refused: the cell needs {chips} chips, JAX sees {len(devices)}")
        return None
    return jax, devices


def import_program(jax):
    """The system under test: the ``repro`` package of this checkout."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core import types as rtypes
    from repro.core.fleet_sharding import fleet_mesh
    from repro.core.policy import SchedulerPolicy
    from repro.core.scan_sim import EventTrace, simulate_scan
    from repro.core.soa_fleet import SoAFleet

    return types.SimpleNamespace(
        jax=jax, types=rtypes, SchedulerPolicy=SchedulerPolicy,
        SoAFleet=SoAFleet, EventTrace=EventTrace, simulate_scan=simulate_scan,
        TraceAnnotation=jax.profiler.TraceAnnotation, fleet_mesh=fleet_mesh,
    )


def policy_of(repro, config, rehearse: bool, chips: int = 1):
    """The configuration's policy; a cell on more than one chip shards the
    fleet host-major over a mesh of its chips."""
    p = config["policy"]
    mesh = {} if chips == 1 else {"mesh": repro.fleet_mesh(chips)}
    return repro.SchedulerPolicy(
        queue_capacity=p["queue_capacity"], admit_batch=p["admit_batch"],
        slo_target_s=p["slo_target_s"], max_retries=p["max_retries"],
        cost_kind=p["cost_kind"], period=p["period_s"],
        fused_screen=True if rehearse else None, **mesh,
    )


class CompileCounter:
    """Programs lowered while ``armed`` (each new jit specialization is
    lowered once, whether or not the persistent cache then holds it)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self, jax):
        self.armed, self.count, self.names = False, 0, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if self.armed and name == self.EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))


def load_reader(name):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Profiler:
    """The profiler over the first ``limit_s`` seconds of a window (a no-op
    when off): a served window holds too many device operations to trace
    whole.  ``wall`` is the traced interval on the host clock."""

    def __init__(self, jax, on: bool, limit_s: float):
        self.jax, self.on, self.limit_s = jax, on, limit_s
        self.running = False
        self.wall = (0.0, 0.0)

    def start(self):
        if self.on:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            os.makedirs(TRACE_DIR, exist_ok=True)
            self.jax.profiler.start_trace(TRACE_DIR)
            self.running = True
            self.wall = (time.perf_counter(), 0.0)

    def tick(self):
        if self.running and time.perf_counter() - self.wall[0] >= self.limit_s:
            self.stop()

    def stop(self):
        if self.running:
            self.wall = (self.wall[0], time.perf_counter())
            self.jax.profiler.stop_trace()
            self.running = False


class HostWatch:
    """What the host did during the window besides this run's work: this
    process's CPU seconds and involuntary context switches, the machine's
    busy and stolen shares of its CPUs (``/proc/stat``), and the full
    garbage collections with their seconds.  ``start`` also collects and
    freezes what set-up left, so that no collection in the window walks
    the fleet's host mirror."""

    def __init__(self):
        self.full, self.full_s, self._t = 0, 0.0, None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.full += 1
            self.full_s += time.perf_counter() - self._t

    @staticmethod
    def _sample():
        ru = resource.getrusage(resource.RUSAGE_SELF)
        try:
            with open("/proc/stat") as f:
                cpu = np.array(f.readline().split()[1:9], np.int64)
        except OSError:
            cpu = np.zeros(8, np.int64)
        return time.perf_counter(), ru.ru_utime + ru.ru_stime, ru.ru_nivcsw, cpu

    def start(self):
        gc.collect()
        gc.freeze()
        self.full, self.full_s = 0, 0.0
        self.first = self._sample()

    def report(self) -> str:
        last = self._sample()
        wall, cpu_s, switches = (b - a for a, b in zip(self.first[:3], last[:3]))
        ticks = last[3] - self.first[3]   # user nice system idle iowait irq softirq steal
        total = max(1, int(ticks.sum()))
        return (
            f"host in the window: process CPU {cpu_s:.3f} s of {wall:.3f} s, "
            f"{switches} involuntary switches; machine busy "
            f"{100.0 * (total - ticks[3] - ticks[4]) / total:.1f}% of "
            f"{os.cpu_count()} CPUs, steal {100.0 * ticks[7] / total:.2f}%; "
            f"{self.full} full collections ({self.full_s:.3f} s)")


def p95_ms(lat_s: np.ndarray) -> float:
    """95th percentile by nearest rank (refused requests count as +inf)."""
    v = np.sort(lat_s)
    x = float(v[max(0, math.ceil(0.95 * v.size) - 1)])
    return x * 1e3


# ---------------------------------------------------------------------------
# modes
# ---------------------------------------------------------------------------


def run_served(ctx, repro, args, config, traffic, rehearse):
    import served

    jax = repro.jax
    mode = traffic["mode"]
    fleet = fleetgen.prefill(config, args.seed)
    sim_rate = fleetgen.little_rate(config, fleet)
    policy = policy_of(repro, config, rehearse, ctx.chips)
    pol = config["policy"]
    if mode == "open":
        r_wall = REHEARSE_WALL_RATE if rehearse else traffic["wall_rate_per_s"]
        count = int(r_wall * (args.seconds * 1.05 + traffic["tail_s"])) + 256
    else:
        count = int(traffic["max_decisions_per_s"] * args.seconds) + 4 * pol["queue_capacity"]
    arr = fleetgen.arrivals(config, sim_rate, count, args.seed)

    top = pol["admit_batch"] if mode == "open" else pol["queue_capacity"]
    served.warm_up(repro, fleet, arr, policy, top)

    s = served.Served(repro, fleet, arr, policy, trace=ctx.prof.on)
    jax.block_until_ready(s.fleet.state)
    ctx.watch.start()
    ctx.setup_s = time.perf_counter() - T_START
    ctx.compiles_counter.armed = True
    ctx.prof.start()
    if mode == "open":
        lapse = r_wall / sim_rate
        due, n_window, w0, late = served.run_open(
            s, lapse, args.seconds, traffic["tail_s"], tick=ctx.prof.tick)
    else:
        decided, submitted, w0 = served.run_backlog(s, args.seconds, tick=ctx.prof.tick)
    ctx.prof.stop()
    ctx.note.append(ctx.watch.report())
    ctx.compiles_counter.armed = False
    ctx.compiles = ctx.compiles_counter.count
    ctx.read_memory()

    # outcomes of the window, then the program's state to the host and freed
    s.stats = s.front.stats
    conserved = s.conserved()
    stt = s.fleet.state
    state = {f: np.asarray(getattr(stt, f)) for f in (
        "free_f", "free_n", "inst_valid", "inst_res", "inst_start",
        "zone_term", "zone_up")}
    ctx.shortlist = dict(s.fleet.shortlist_stats)
    ctx.shape = (fleet.n, fleet.k_slots, fleet.cap.shape[0], ctx.shortlist["shortlist"])
    del stt
    s.fleet = s.front = None

    t_w0, t_w1 = ctx.prof.wall
    ctx.traced_decisions = int(np.sum((s.decided_at >= t_w0) & (s.decided_at <= t_w1)))
    if mode == "open":
        window = slice(0, n_window)
        lat = s.decided_at[window] - (w0 + due[window])
        lat[s.overflow[window]] = np.inf
        lat[np.isnan(lat)] = np.inf
        ctx.attempted = n_window
        ctx.failed = int(np.sum(s.overflow[window]))
        ctx.e2e = {"admit_p95_ms": p95_ms(lat)}
        fin = lat[np.isfinite(lat)]
        lt = late[window]
        ctx.note.append(
            f"open loop: {n_window} requests due in {args.seconds} s at "
            f"{r_wall} /s (lapse {lapse:.1f} sim s per s); p50 "
            f"{np.median(fin) * 1e3 if fin.size else float('nan'):.3f} ms, "
            f"p95 {ctx.e2e['admit_p95_ms']:.3f} ms over {lat.size} samples; "
            f"{ctx.failed} refused as the queue was full; drains "
            f"{s.stats.drains}; generator late p50 {np.nanmedian(lt) * 1e3:.3f} ms "
            f"max {np.nanmax(lt) * 1e3:.3f} ms")
        ctx.note.append(f"outstanding (wall s, due and undecided): {s.outstanding}")
        ctx.note.append(
            "longest loop step %.3f s at %.3f s (%s); longest device wait %.3f s"
            % (*s.longest_step, s.longest_wait))
    else:
        ctx.attempted = submitted
        ctx.failed = int(np.sum(s.overflow))
        ctx.e2e = {"decisions_per_s": decided / args.seconds}
        ctx.note.append(
            f"backlog: {decided} decisions in {args.seconds} s; "
            f"{submitted} submitted; drains {s.stats.drains}")
    dropped = s.stats.rejected_retry
    ctx.note.append(
        f"admission: arrivals {s.stats.arrivals} admitted {s.stats.admitted} "
        f"refused by the scheduler {dropped} "
        f"({100.0 * dropped / max(1, s.stats.admitted + dropped):.3f}% of decisions) "
        f"queue full {s.stats.rejected_overflow} retries {s.stats.retries}")
    if not conserved:
        ctx.failed += 1
        ctx.note.append("admission conservation broken")
    ctx.raised = ctx.raised or not conserved

    ctx.served, ctx.state, ctx.fleet = s, state, fleet
    t = time.perf_counter()
    ctx.checks = served.check(s, state, fleet, config)
    ctx.note.append(f"reference replay took {time.perf_counter() - t:.3f} s")
    return ctx


def run_whatif(ctx, repro, args, config, traffic, rehearse):
    import whatif

    jax = repro.jax
    fleet = fleetgen.prefill(config, args.seed)
    span = config["whatif_span_s"]
    sim_rate = fleetgen.little_rate(config, fleet)
    policy = policy_of(repro, config, rehearse, ctx.chips)
    trace = whatif.make_trace(config, sim_rate, span, args.seed)
    state0 = whatif.start_state(repro, fleet, policy)
    et = whatif.event_trace(repro, trace)
    res = repro.simulate_scan(et, policy, state0)          # compiles
    jax.block_until_ready(res.state)
    del res
    ctx.watch.start()
    ctx.setup_s = time.perf_counter() - T_START
    ctx.compiles_counter.armed = True
    n_real = trace.n_real
    dispatches = 0
    traced_dispatches = 0
    w0 = time.perf_counter()
    while True:
        first = dispatches == 0
        if first:
            ctx.prof.start()
        res = repro.simulate_scan(et, policy, state0)
        jax.block_until_ready(res.state)
        if first:
            ctx.prof.stop()
            traced_dispatches = 1 if ctx.prof.on else 0
        dispatches += 1
        if time.perf_counter() - w0 >= args.seconds:
            break
    elapsed = time.perf_counter() - w0
    ctx.note.append(ctx.watch.report())
    ctx.compiles_counter.armed = False
    ctx.compiles = ctx.compiles_counter.count
    ctx.read_memory()
    out = whatif.to_host(res)
    ctx.shape = (fleet.n, fleet.k_slots, fleet.cap.shape[0], 64 if fleet.n > 256 else 0)
    del res, state0
    ctx.attempted = n_real * dispatches
    ctx.failed = 0
    ctx.traced_events = n_real * traced_dispatches
    ctx.e2e = {"sim_events_per_s": ctx.attempted / elapsed}
    ctx.note.append(
        f"what-if: {dispatches} dispatches of {n_real} events "
        f"({trace.n_arrivals} arrivals, {span} simulated s) in {elapsed:.3f} s; "
        f"one dispatch {elapsed / dispatches:.3f} s")
    ctx.out, ctx.trace_obj, ctx.fleet = out, trace, fleet
    t = time.perf_counter()
    ctx.checks = whatif.check(out, trace, fleet, config)
    ctx.note.append(f"reference replay took {time.perf_counter() - t:.3f} s")
    return ctx


def run_batch(ctx, repro, args, config, traffic, rehearse):
    import batch

    fleet = fleetgen.prefill(config, args.seed)
    rate = fleetgen.little_rate(config, fleet)
    policy = policy_of(repro, config, rehearse, ctx.chips)
    count = int(traffic["max_decisions_per_s"] * args.seconds)
    jobs = fleetgen.batch_jobs(config, traffic, rate, count, args.seed)
    b = batch.Batch(repro, fleet, jobs, policy, trace=ctx.prof.on)
    batch.warm_up(b, fleet, traffic["job_sizes"])
    t_warm = time.perf_counter() - T_START
    ctx.watch.start()
    ctx.setup_s = time.perf_counter() - T_START
    ctx.compiles_counter.armed = True
    ctx.prof.start()
    decided, elapsed, ran_out = batch.run_window(b, args.seconds, tick=ctx.prof.tick)
    ctx.prof.stop()
    ctx.note.append(ctx.watch.report())
    ctx.compiles_counter.armed = False
    ctx.compiles = ctx.compiles_counter.count
    ctx.read_memory()

    # the program's state to the host (without its padding rows) and freed
    stt = b.fleet.state
    state = {f: np.asarray(getattr(stt, f))[:fleet.n] for f in (
        "free_f", "free_n", "inst_valid", "inst_res", "inst_start")}
    state.update({f: np.asarray(getattr(stt, f)) for f in ("zone_term", "zone_up")})
    ctx.shortlist = dict(b.fleet.shortlist_stats)
    ctx.shape = (stt.n_hosts // ctx.chips, fleet.k_slots, fleet.cap.shape[0],
                 ctx.shortlist["shortlist"])
    del stt
    b.fleet = None

    t_w1 = ctx.prof.wall[1]
    ctx.traced_decisions = sum(n for at, n, _ in b.done if at <= t_w1)
    ctx.traced_rows = sum(r for at, _, r in b.done if at <= t_w1)
    ctx.attempted = decided
    ctx.failed = b.lost + int(ran_out)
    ctx.e2e = {"decisions_per_s": decided / elapsed}
    rows = sum(r for _, _, r in b.done)
    placed = sum(o[0] for op in b.ops if op[0] == "job" for o in op[2])
    ctx.note.append(
        f"batch: {b.sent} jobs, {decided} decisions ({placed} placed, "
        f"{decided - placed} refused) in {elapsed:.3f} s over {ctx.chips} "
        f"chip(s); {rows} scan rows, {100.0 * (1 - decided / max(1, rows)):.2f}% "
        f"padding; simulated {jobs.t[0]:.0f}-{jobs.t[max(0, b.sent - 1)]:.0f} s; "
        f"{b.departed} departures; set-up to the end of warm-up {t_warm:.3f} s")
    if ran_out:
        ctx.note.append(f"the traffic's {len(jobs)} jobs ran out inside the window")
    if b.lost:
        ctx.note.append(f"{b.lost} requests came back with no outcome")
    ctx.batch, ctx.state, ctx.fleet = b, state, fleet
    t = time.perf_counter()
    ctx.checks = batch.check(b, state, fleet, config)
    ctx.note.append(f"reference replay took {time.perf_counter() - t:.3f} s")
    return ctx


MODES = {"open": run_served, "backlog": run_served, "whatif": run_whatif,
         "batch": run_batch}


def execute(argv=None):
    """Parse the arguments and run the cell; returns (context, arguments,
    BENCHMARK.json, devices), or None when the machine is refused."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--chips", type=int, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    spec = load_json(ROOT, "BENCHMARK.json")
    cell = cell_of(spec, args.workload)
    config = load_json(BENCH, "configs", f"{cell['config']}.json")
    traffic = load_json(BENCH, "traffic", f"{cell['traffic']}.json")
    if args.rehearse:
        config = dict(config, hosts=REHEARSE_HOSTS)
    chips = cell["chips"] if args.chips is None else args.chips
    started = start_jax(args.rehearse, chips)
    if started is None:
        return None
    jax, devices = started
    repro = import_program(jax)
    dev = devices[0]

    ctx = types.SimpleNamespace(
        note=[], raised=False, summary=None, traced_decisions=0,
        traced_events=0, shortlist={}, compiles=None,
        device_kind=dev.device_kind, memory_peak=0, chips=chips,
    )
    ctx.compiles_counter = CompileCounter(jax)
    ctx.watch = HostWatch()
    ctx.prof = Profiler(jax, bool(args.trace), traffic.get("trace_s", args.seconds))

    def read_memory():
        """Peak bytes on the fullest chip of the cell's."""
        ctx.memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                              for d in devices[:chips])

    ctx.read_memory = read_memory
    ctx.config = config
    ctx.attempted, ctx.failed, ctx.e2e, ctx.checks = 0, 0, {}, {}
    try:
        MODES[traffic["mode"]](ctx, repro, args, config, traffic, args.rehearse)
    except Exception:  # the system under test raised: the run is not correct
        ctx.prof.stop()
        ctx.raised = True
        ctx.failed += 1
        ctx.checks = dict(ctx.checks, raised=1)
        ctx.note.append("the program raised:\n" + traceback.format_exc()[-3000:])
        if not hasattr(ctx, "setup_s"):
            ctx.setup_s = time.perf_counter() - T_START
    return ctx, args, spec, devices


def main(argv=None) -> int:
    done = execute(argv)
    if done is None:
        return 2
    ctx, args, spec, devices = done
    dev = devices[0]
    metrics = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": ctx.memory_peak}
    line = {"correct": False, "attempted": ctx.attempted, "failed": ctx.failed}
    if args.trace and not ctx.raised:
        import tracing

        t0, t1 = ctx.prof.wall
        ctx.summary = tracing.summarize(TRACE_DIR, t1 - t0)
        ctx.trace_dir = TRACE_DIR
        for m in spec["per_layer"]:
            if args.workload not in m.get("workloads", [args.workload]):
                continue
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = ctx.summary.busy or 0.0
        device["window_s"] = ctx.summary.window_s
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in ctx.summary.top_ops()],
            "idle_gaps": [[n, s] for n, s in ctx.summary.idle_gaps()][:10],
        }
    elif not args.trace:
        for m in spec["end_to_end"]:
            if args.workload not in m.get("workloads", [args.workload]):
                continue
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": ctx.setup_s, "unit": "s"}
            elif m["name"] in ctx.e2e:
                metrics[m["name"]] = {"value": ctx.e2e[m["name"]], "unit": m["unit"]}
    checks = {k: {"value": int(v), "limit": LIMITS[k]} for k, v in ctx.checks.items()}
    line["correct"] = (not ctx.raised) and all(
        c["value"] <= c["limit"] for c in checks.values())
    line["metrics"] = metrics
    line["device"] = device
    line["checks"] = checks
    for n in ctx.note:
        log(n)
    log(f"setup_s {ctx.setup_s:.3f}; memory_peak_bytes {ctx.memory_peak}; "
        f"compiles in window {ctx.compiles} {ctx.compiles_counter.names[:8]}")
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
