"""Chip smoke test: the scheduler's main paths, end to end, on a TPU.

    python chip_smoke.py [--seed S]          # one chip (default)
    python chip_smoke.py --chips 4 [--seed S]  # the sharded fleet only

One process drives every phase; a machine whose first JAX device is not a
TPU is refused with a non-zero exit and no result line.  Phases, in order:

  reference  a ~512-host fleet decided by the device path and by the python
             ``PreemptibleScheduler`` (same comparison as
             tests/test_jax_scheduler.py);
  served     ``SoAFleet.submit`` -> drain -> placement at 65 536 hosts, K=8,
             with the default execution knobs (so the compiled stage-1
             kernel decides); checked for admission conservation, at least
             one preemption, the kernel in the compiled drain, and bitwise
             agreement with the same attempts replayed through
             ``schedule_many`` on the jnp screen;
  scan       one ``simulate_scan`` dispatch at 4 096 hosts, bitwise against
             ``SoASimulator.run_trace``; one at 65 536 hosts run to the end;
             one 8-lane ``simulate_ensemble`` at 4 096 hosts;
  sharded    (``--chips 4`` only) a 2^20-host fleet sharded over every chip,
             decisions bitwise against the same fleet on one chip.

Every line before the last is smoke output prefixed ``[smoke]``: versions,
wall times (compile included where marked) and counts.  They are not
benchmark numbers.  The last line is one JSON object naming the device.  Any
failed check raises, so the script exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import os
import statistics
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import (  # noqa: E402
    NODE_CAP, NOW, SIZES, saturated_fleet, use_compile_cache,
)
from repro.core.admission import PAD_RES, _drain_donated  # noqa: E402
from repro.core.cost import PeriodCost  # noqa: E402
from repro.core.fleet_sharding import fleet_mesh  # noqa: E402
from repro.core.jax_scheduler import (  # noqa: E402
    JaxPreemptibleScheduler, _many_donated,
)
from repro.core.policy import SchedulerPolicy  # noqa: E402
from repro.core.scan_sim import (  # noqa: E402
    ARRIVAL, simulate_ensemble, simulate_scan, trace_from_workload,
)
from repro.core.scheduler import PreemptibleScheduler  # noqa: E402
from repro.core.simulator import SoASimulator, WorkloadSpec  # noqa: E402
from repro.core.soa_fleet import SoAFleet  # noqa: E402
from repro.core.types import Host, Instance, Request  # noqa: E402

K_SLOTS = 8
#: the served policy: a 256-deep admission queue drained 64 at a time
SERVED_POLICY = SchedulerPolicy(
    queue_capacity=256, admit_batch=64, slo_target_s=60.0
)
#: request mix: flavour probabilities (small, medium, large), half preemptible
FLAVOR_PROBS = (0.4, 0.4, 0.2)
SCAN_WORKLOAD = WorkloadSpec(
    arrival_rate_per_s=1 / 4.0,
    flavors=list(SIZES.items()),
    flavor_probs=FLAVOR_PROBS,
    preemptible_fraction=0.5,
)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def prefill_fleet(n_hosts: int, seed: int, free_frac: float = 0.125):
    """Table 1 nodes filled to near saturation: ``saturated_fleet`` (four
    medium instances per node, normal/preemptible mix, integer-minute ages)
    with one instance removed from a seeded ``free_frac`` of the nodes, so
    preemptible work still finds room and large interactive work must
    preempt."""
    hosts = saturated_fleet(n_hosts, seed=seed)
    rng = np.random.default_rng(seed + 1)
    for i in np.flatnonzero(rng.random(n_hosts) < free_frac):
        hosts[i].remove(next(iter(hosts[i].instances)))
    return hosts


def request_stream(n: int, seed: int, start: float = NOW):
    """Seeded ``(request, arrival_time)`` pairs: small/medium/large, half
    preemptible (batch) and half normal (interactive), integer-second
    arrivals about two per second."""
    rng = np.random.default_rng(seed)
    names = list(SIZES)
    out, t = [], start
    for i in range(n):
        t += float(rng.integers(0, 2))
        size = names[int(rng.choice(len(names), p=FLAVOR_PROBS))]
        req = Request(
            id=f"r{i}", resources=SIZES[size],
            preemptible=bool(rng.random() < 0.5),
        )
        out.append((req, t))
    return out


def _states_equal(a, b, n_rows=None) -> None:
    """Every column of two fleet states, bitwise (host-indexed columns cut
    to the first ``n_rows`` rows when one state is padded)."""
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if x is None or y is None:
            assert x is None and y is None, f"state column {f.name} presence"
            continue
        x, y = np.asarray(x), np.asarray(y)
        if n_rows is not None and f.name not in ("zone_term", "zone_up"):
            x, y = x[:n_rows], y[:n_rows]
        assert np.array_equal(x, y), f"state column {f.name} diverged"


def _outcomes_equal(got, want, what: str) -> None:
    """Two ``SoAOutcome`` sequences name the same host, instance, slot and
    victims, in order."""
    assert len(got) == len(want), f"{what}: {len(got)} vs {len(want)}"
    for a, b in zip(got, want):
        assert a.ok == b.ok, f"{what}: {a.request.id} placed differs"
        if not a.ok:
            continue
        assert a.host == b.host, f"{what}: {a.request.id} host differs"
        assert a.instance.id == b.instance.id, f"{what}: instance id differs"
        assert a.instance.metadata.get("slot") == b.instance.metadata.get(
            "slot"
        ), f"{what}: {a.request.id} slot differs"
        assert [v.id for v in a.victims] == [v.id for v in b.victims], (
            f"{what}: {a.request.id} victims differ"
        )


# ---------------------------------------------------------------------------
# Phase: device decisions against the python reference scheduler
# ---------------------------------------------------------------------------


def reference_phase(n_hosts: int = 512, n_requests: int = 200, seed: int = 0):
    """Decide a seeded stream on a near-saturated fleet with the device
    path (``JaxPreemptibleScheduler``, default policy) and the python
    ``PreemptibleScheduler``, applying the python decision after each
    request.  The comparison is tests/test_jax_scheduler.py's: same ok,
    termination cost within 1e-2, and the same victims unless the two
    picked different hosts at an exact cost tie."""
    hosts = prefill_fleet(n_hosts, seed)
    by_name = {h.name: h for h in hosts}
    py = PreemptibleScheduler(cost_fn=PeriodCost())
    py._rng = np.random.default_rng(0)
    jx = JaxPreemptibleScheduler(cost_fn=PeriodCost(), k_slots=K_SLOTS)
    placed = preemptions = host_ties = 0
    t0 = time.perf_counter()
    for req, now in request_stream(n_requests, seed + 3):
        r_py = py.schedule(req, hosts, now)
        r_jx = jx.schedule(req, hosts, now)
        assert r_py.ok == r_jx.ok, f"reference: {req.id} ok differs"
        if not r_py.ok:
            continue
        assert abs(r_jx.plan.cost - r_py.plan.cost) <= 1e-2, (
            f"reference: {req.id} cost {r_jx.plan.cost} vs {r_py.plan.cost}"
        )
        if abs(r_py.plan.cost - r_jx.plan.cost) < 1e-6 and r_py.host != r_jx.host:
            host_ties += 1
        else:
            assert set(r_jx.plan.ids) == set(r_py.plan.ids), (
                f"reference: {req.id} victims differ"
            )
        host = by_name[r_py.host]
        for vid in r_py.plan.ids:
            host.remove(vid)
        host.place(Instance(
            id=f"i-{req.id}", resources=req.resources,
            preemptible=req.preemptible, host=host.name, start_time=now,
        ))
        placed += 1
        preemptions += len(r_py.plan.ids)
    return {
        "requests": n_requests, "placed": placed, "preemptions": preemptions,
        "host_ties": host_ties, "wall_s": time.perf_counter() - t0,
    }


# ---------------------------------------------------------------------------
# Phase: the served path (submit -> drain -> placement)
# ---------------------------------------------------------------------------


def _drain_text(fleet: SoAFleet, now: float) -> str:
    """Compiled text of the fused drain program the front end dispatches
    (the donated variant, at a 64-row arrival buffer)."""
    a, d = 64, fleet.state.free_f.shape[1]
    bufs = (
        np.full((a, d), PAD_RES, np.float32), np.zeros((a,), bool),
        np.full((a,), -1, np.int32), np.full((a,), -1, np.int32),
        np.full((a,), -1.0, np.float32), np.full((a,), -1, np.int32),
        np.zeros((a,), np.int32), np.zeros((a,), np.float32),
        np.ones((a,), np.float32), np.zeros((a,), bool),
    )
    lowered = _drain_donated.lower(
        fleet.state, fleet.admission.qstate, *bufs, jnp.float32(now),
        policy=fleet.policy,
    )
    return lowered.compile().as_text()


def served_phase(n_hosts: int = 65_536, n_requests: int = 640, seed: int = 0):
    """Stream ``n_requests`` through ``SoAFleet.submit`` with batch-full and
    SLO-deadline drains, settle with ``drain_all``, and check the result."""
    t0 = time.perf_counter()
    fleet = SoAFleet(
        prefill_fleet(n_hosts, seed), k_slots=K_SLOTS, policy=SERVED_POLICY
    )
    replay = SoAFleet(
        prefill_fleet(n_hosts, seed), k_slots=K_SLOTS,
        policy=SchedulerPolicy(fused_screen=False),
    )
    build_s = time.perf_counter() - t0
    front = fleet.admission
    drains, walls = [], []

    def drain(now):
        t = time.perf_counter()
        drains.append(fleet.drain(now))
        walls.append(time.perf_counter() - t)

    stream = request_stream(n_requests, seed + 2)
    for req, t in stream:
        deadline = front.next_deadline()
        if deadline is not None and t >= deadline:
            drain(t)
        fleet.submit(req, t)
        if front.batch_ready():
            drain(t)
    t_end = stream[-1][1] + 1.0
    t = time.perf_counter()
    drains += fleet.drain_all(t_end)
    drain_all_s = time.perf_counter() - t

    st = front.stats
    assert st.arrivals == n_requests
    assert front.pending == 0
    assert st.arrivals == st.admitted + st.rejected + st.queue_depth, (
        "admission conservation broken"
    )
    served = [o for dr in drains for o in dr.outcomes]
    assert len(served) == st.admitted
    preemptions = sum(len(o.victims) for o in served)
    assert preemptions >= 1, "the stream never preempted"

    # Replay every attempt, in drain order at its drain time, as one
    # schedule_many scan on the jnp screen; decisions and the final fleet
    # arrays must match the drained run bitwise.
    attempts = [(req, dr.now, 1.0) for dr in drains for req, _ in dr.attempts]
    t = time.perf_counter()
    replayed = replay.schedule_batch(attempts)
    replay_s = time.perf_counter() - t
    assert [o.ok for o in replayed] == [
        ok for dr in drains for _, ok in dr.attempts
    ], "replay placed a different set"
    _outcomes_equal([o for o in replayed if o.ok], served, "served vs replay")
    _states_equal(fleet.state, replay.state)

    kernel = "tpu_custom_call" in _drain_text(fleet, t_end)
    return {
        "hosts": n_hosts, "requests": n_requests, "placed": st.admitted,
        "rejected": st.rejected, "preemptions": preemptions,
        "drains": st.drains, "attempts": len(attempts),
        "kernel_in_drain": kernel, "build_s": build_s,
        "first_drain_s": walls[0],
        "drain_median_s": statistics.median(walls[1:] or walls),
        "drain_all_s": drain_all_s, "replay_s": replay_s,
    }


# ---------------------------------------------------------------------------
# Phase: the scanned simulator
# ---------------------------------------------------------------------------


def _snapshot(state):
    """Deep copy: the python oracle's donated transitions consume buffers."""
    return jax.tree_util.tree_map(lambda a: jnp.asarray(np.asarray(a)), state)


def _scan_trace(seed: int, duration_s: float):
    return trace_from_workload(SCAN_WORKLOAD, duration_s, seed=seed)


def _counters_conserve(counters, trace, what: str) -> int:
    arrivals = int(np.sum(trace.kind == ARRIVAL))
    decided = sum(
        counters[k] for k in ("placed_normal", "placed_preemptible",
                              "failures_normal", "failures_preemptible")
    )
    assert decided == arrivals, f"{what}: {decided} decisions, {arrivals} arrivals"
    return arrivals


def scan_phase(n_hosts: int = 4_096, seed: int = 0, duration_s: float = 2_800.0,
               oracle: bool = True):
    """One ``simulate_scan`` dispatch on a near-saturated fleet (default
    policy).  With ``oracle`` the same trace replays through
    ``SoASimulator.run_trace`` and every counter, sample, placement and
    final fleet array must match bitwise."""
    policy = SchedulerPolicy()
    trace = _scan_trace(seed, duration_s)
    sim = SoASimulator(
        prefill_fleet(n_hosts, seed), SCAN_WORKLOAD, seed=seed,
        k_slots=K_SLOTS, policy=policy,
    )
    state0 = _snapshot(sim.fleet.state)
    t = time.perf_counter()
    dev = simulate_scan(trace, policy, state0)
    jax.block_until_ready(dev.state)
    scan_s = time.perf_counter() - t
    arrivals = _counters_conserve(dev.counters, trace, "scan")
    out = {
        "hosts": n_hosts, "events": trace.n_events, "arrivals": arrivals,
        "scan_s": scan_s, **dev.counters,
    }
    if oracle:
        t = time.perf_counter()
        m_py = sim.run_trace(trace)
        out["oracle_s"] = time.perf_counter() - t
        _states_equal(sim.fleet.state, dev.state)
        seq = np.stack(
            [dev.host, dev.slot, dev.ok.astype(np.int64), dev.n_kill], axis=1
        )
        assert np.array_equal(seq, sim.trace_outcomes), "placements diverged"
        m_dev = dev.sim_metrics(sim.fleet._cap0_total)
        for name in dev.counters:
            assert getattr(m_py, name) == getattr(m_dev, name), name
        assert m_py.t == m_dev.t
        assert m_py.utilization == m_dev.utilization
        assert m_py.utilization_normal == m_dev.utilization_normal
    return out


def ensemble_phase(n_hosts: int = 4_096, lanes: int = 8, seed: int = 0,
                   duration_s: float = 2_800.0):
    """One ``simulate_ensemble`` dispatch over ``lanes`` seeded traces.  The
    ensemble runs the jnp stage-1 screen by design (the Pallas kernels take
    no batch axis)."""
    policy = SchedulerPolicy()
    traces = [_scan_trace(seed + i, duration_s) for i in range(lanes)]
    fleet = SoAFleet(prefill_fleet(n_hosts, seed), k_slots=K_SLOTS,
                     policy=policy)
    t = time.perf_counter()
    results = simulate_ensemble(traces, policy, fleet.state)
    ens_s = time.perf_counter() - t
    for i, (res, tr) in enumerate(zip(results, traces)):
        _counters_conserve(res.counters, tr, f"lane {i}")
    return {
        "hosts": n_hosts, "lanes": lanes,
        "events": sum(tr.n_events for tr in traces), "ensemble_s": ens_s,
        "preemptions": sum(r.counters["preemptions"] for r in results),
    }


# ---------------------------------------------------------------------------
# Phase: the sharded fleet (--chips 4)
# ---------------------------------------------------------------------------


def sharded_fleet_hosts(n_hosts: int, seed: int, every: int = 16):
    """``n_hosts`` Table 1 nodes; every ``every``-th one comes from a
    seeded near-saturated fleet, the rest are empty."""
    full = iter(prefill_fleet(-(-n_hosts // every), seed))
    return [
        next(full) if i % every == 0
        else Host(name=f"e{i}", capacity=NODE_CAP)
        for i in range(n_hosts)
    ]


def sharded_phase(n_hosts: int = 1 << 20, n_requests: int = 384,
                  seed: int = 0, mesh=None):
    """The same seeded requests through ``schedule_batch`` (one
    ``schedule_many`` scan) on a fleet sharded over ``mesh`` and on the
    unsharded fleet; decisions and fleet arrays must match bitwise, and
    the sharded program must hold the per-shard screen's all-gather."""
    mesh = fleet_mesh() if mesh is None else mesh
    hosts = sharded_fleet_hosts(n_hosts, seed)
    t = time.perf_counter()
    sharded = SoAFleet(hosts, k_slots=K_SLOTS,
                       policy=SchedulerPolicy(mesh=mesh))
    single = SoAFleet(hosts, k_slots=K_SLOTS, policy=SchedulerPolicy())
    build_s = time.perf_counter() - t
    devices = sharded.state.free_f.sharding.device_set
    assert len(devices) == mesh.size > 1, "fleet state is not sharded"

    items = [(req, t, 1.0) for req, t in request_stream(n_requests, seed + 5)]
    b = 1 << (len(items) - 1).bit_length()
    lowered = _many_donated.lower(
        sharded.state, np.ones((b, NODE_CAP.vec.size), np.float32),
        np.zeros((b,), bool), np.full((b,), -1, np.int32),
        np.full((b,), NOW, np.float32), np.ones((b,), np.float32),
        np.full((b,), -1, np.int32), np.full((b,), -1.0, np.float32),
        np.full((b,), -1, np.int32), policy=sharded.policy,
    )
    assert "all_gather" in lowered.as_text(), "the mesh screen did not run"

    t = time.perf_counter()
    got = sharded.schedule_batch(items)
    sharded_s = time.perf_counter() - t
    t = time.perf_counter()
    want = single.schedule_batch(items)
    single_s = time.perf_counter() - t
    _outcomes_equal(got, want, "sharded vs one chip")
    _states_equal(sharded.state, single.state, n_rows=n_hosts)
    return {
        "hosts": n_hosts, "shards": mesh.size, "requests": n_requests,
        "placed": sum(o.ok for o in got),
        "preemptions": sum(len(o.victims) for o in got),
        "build_s": build_s, "sharded_batch_s": sharded_s,
        "single_batch_s": single_s,
    }


# ---------------------------------------------------------------------------


def _versions() -> str:
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    import jaxlib

    return f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu {libtpu}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the sharded-fleet phase over 4 chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[smoke] refused: first device is {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"[smoke] refused: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 2
    cache = use_compile_cache()
    log(f"smoke output, not benchmark numbers; {_versions()}")
    log(f"devices: {len(devices)} x {dev.device_kind}; compile cache {cache}")

    if args.chips == 4:
        phases = [("sharded", lambda: sharded_phase(
            seed=args.seed, mesh=fleet_mesh(4)))]
    else:
        phases = [
            ("reference", lambda: reference_phase(seed=args.seed)),
            ("served", lambda: served_phase(seed=args.seed)),
            ("scan_n4096", lambda: scan_phase(seed=args.seed)),
            ("scan_n65536", lambda: scan_phase(
                n_hosts=65_536, seed=args.seed, oracle=False)),
            ("ensemble_n4096_l8", lambda: ensemble_phase(seed=args.seed)),
        ]
    for name, run in phases:
        t = time.perf_counter()
        out = run()
        out["phase_wall_s"] = time.perf_counter() - t
        log(f"{name}: " + json.dumps(out, default=float))
        if name == "served":
            assert out["placed"] >= 512, "served phase placed < 512 requests"
            assert out["kernel_in_drain"], (
                "compiled drain holds no tpu_custom_call: the stage-1 "
                "kernel did not compile into the served path"
            )
        if name == "ensemble_n4096_l8":
            log("ensemble ran the jnp stage-1 screen (by design)")

    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
