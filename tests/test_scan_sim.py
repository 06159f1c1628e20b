"""Differential parity suite for the fully on-device scanned simulator.

``core.scan_sim.simulate_scan`` folds the ENTIRE event stream — arrivals
(mixed cost kinds / periods / priorities), departures, host failures and
heals, zone storms, checkpoints — into one jitted ``lax.scan``.  This suite
pins it **bit-exact** against the python ``SoASimulator`` oracle
(``run_trace``), which replays the identical ``EventTrace`` through the
seven-PR-old incremental fleet path:

  * final fleet-state arrays equal bitwise (every column, dead-slot
    payloads included);
  * per-arrival placement/rejection sequences identical (host, slot, ok,
    victim count per event);
  * every ``SimMetrics`` counter equal and every sample-point utilization
    reading equal bitwise (integer-resource f32 sums are exact under any
    association, so fused device reductions == sequential python adds);
  * resources are conserved at every sample point and at the end.

Randomness is a SEEDED SWEEP (``PARITY_SEEDS`` / property-style generators
with explicit ``np.random.default_rng`` seeds) — no hypothesis dependency,
no environment probing, NO skip paths: every test in this file always runs,
and CI gates the suite fail-on-skip next to the other parity gates.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scan_sim as ss
from repro.core.policy import COST_KINDS, SchedulerPolicy
from repro.core.scan_sim import (
    ARRIVAL,
    EventTrace,
    TraceEvent,
    simulate_ensemble,
    simulate_scan,
    trace_from_workload,
)
from repro.core.simulator import SoASimulator, WorkloadSpec
from repro.core.types import VM_SPEC, Host

CAP = VM_SPEC.make(vcpus=8, ram_mb=16000, disk_gb=160)
SIZES = [
    VM_SPEC.make(vcpus=1, ram_mb=2000, disk_gb=20),
    VM_SPEC.make(vcpus=2, ram_mb=4000, disk_gb=40),
    VM_SPEC.make(vcpus=4, ram_mb=8000, disk_gb=80),
]
K = 8

#: the seeded sweep driving the randomized differential cases
PARITY_SEEDS = (1, 2, 3, 5)

#: every device-resident billing kind in one mixed table
MIXED_POLICY = SchedulerPolicy(
    cost_kind="period",
    cost_kinds=("count", "revenue", "recompute"),
)


def _hosts(n: int, n_zones: int = 3):
    return [
        Host(
            name=f"h{i}", capacity=CAP, domain=f"dom{i % 2}",
            zone=f"z{i % n_zones}",
        )
        for i in range(n)
    ]


def _workload(rate: float = 1 / 20.0, frac: float = 0.6) -> WorkloadSpec:
    return WorkloadSpec(
        arrival_rate_per_s=rate,
        flavors=[(f"f{i}", s) for i, s in enumerate(SIZES)],
        preemptible_fraction=frac,
    )


def _snapshot(state):
    """Deep-copy a fleet state: the python loop's donated transitions
    consume the original buffers."""
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(np.asarray(a)), state
    )


def _rich_trace(seed: int, duration: float = 8000.0,
                n_hosts: int = 16) -> EventTrace:
    """A randomized all-kinds trace: mixed billing, mixed priorities,
    storms in every zone, a mid-run host failure + heal, periodic
    checkpoints.  Always 300+ events at the default duration/rate."""
    rng = np.random.default_rng(seed * 7919)
    storms = [
        (float(rng.integers(int(duration * 0.2), int(duration * 0.9))),
         int(z), float(f))
        for z, f in zip(range(3), (0.5, 0.3, 0.8))
    ]
    failures = [
        (float(rng.integers(int(duration * 0.3), int(duration * 0.6))),
         int(rng.integers(0, n_hosts)), duration * 0.15),
    ]
    return trace_from_workload(
        _workload(), duration, seed=seed,
        storms=storms, failures=failures, checkpoint_every=3,
        cost_kinds=(-1, 0, 1, 2, 3, 1, -1, 3),
        priorities=(-1, 0, 1, 2),
    )


def _assert_bitwise_equal(py_sim: SoASimulator, dev: ss.ScanResult,
                          m_py, trace: EventTrace) -> None:
    # 1. final fleet-state arrays, every column bitwise
    for f in dataclasses.fields(py_sim.fleet.state):
        a = np.asarray(getattr(py_sim.fleet.state, f.name))
        b = np.asarray(getattr(dev.state, f.name))
        assert np.array_equal(a, b), f"state column {f.name} diverged"
    # 2. per-arrival placement/rejection sequence
    seq_dev = np.stack(
        [dev.host, dev.slot, dev.ok.astype(np.int64), dev.n_kill], axis=1
    )
    assert np.array_equal(seq_dev, py_sim.trace_outcomes), (
        "placement/rejection sequences diverged"
    )
    # 3. SimMetrics: every counter + every sample reading
    m_dev = dev.sim_metrics(py_sim.fleet._cap0_total)
    for name in (
        "placed_normal", "placed_preemptible", "failures_normal",
        "failures_preemptible", "preemptions", "storms", "storm_kills",
    ):
        assert getattr(m_py, name) == getattr(m_dev, name), name
    assert m_py.t == m_dev.t
    assert m_py.utilization == m_dev.utilization
    assert m_py.utilization_normal == m_dev.utilization_normal
    # 4. conservation at every sample point: the used capacity implied by
    #    each sample stays within [0, cap] on both engines (they are equal
    #    bitwise by now) ...
    cap = py_sim.fleet._cap0_total
    for u in m_dev.utilization:
        assert 0.0 <= u <= 1.0 + 1e-12
    # ... and exactly at the end: per host, free + live preemptible + live
    #     normal == capacity, cross-checked against the python mirror.
    free = np.asarray(dev.state.free_f)
    used_pre = np.asarray(
        jnp.sum(
            jnp.where(
                dev.state.inst_valid[:, :, None], dev.state.inst_res, 0.0
            ),
            axis=1,
        )
    )
    used_norm = np.zeros_like(free)
    for iid, (h, slot) in py_sim.fleet.locator.items():
        if slot is None:
            used_norm[h] += py_sim.fleet.instances[iid].resources.vec32
    total = free + used_pre + used_norm
    cap_vec = np.asarray(CAP.vec32)
    assert np.array_equal(total, np.broadcast_to(cap_vec, total.shape)), (
        "resource conservation violated at end of trace"
    )


def _run_both(trace: EventTrace, policy: SchedulerPolicy, n_hosts: int,
              seed: int = 0):
    sim = SoASimulator(
        _hosts(n_hosts), _workload(), seed=seed, k_slots=K, policy=policy
    )
    state0 = _snapshot(sim.fleet.state)
    m_py = sim.run_trace(trace)
    dev = simulate_scan(trace, policy, state0)
    return sim, dev, m_py


# ---------------------------------------------------------------------------
# 1. the headline differential sweep: all kinds, mixed billing, randomized
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", PARITY_SEEDS)
def test_scan_parity_randomized_all_kinds(seed):
    trace = _rich_trace(seed)
    assert trace.n_events >= 300, "sweep must exercise 300+ events"
    kinds = set(np.unique(trace.kind).tolist())
    assert {ss.ARRIVAL, ss.DEPARTURE, ss.FAIL_HOST, ss.HEAL_HOST,
            ss.CHECKPOINT, ss.ZONE_STORM} <= kinds
    assert len(set(np.unique(trace.cost_kind).tolist())) >= 4
    sim, dev, m_py = _run_both(trace, MIXED_POLICY, n_hosts=16, seed=seed)
    _assert_bitwise_equal(sim, dev, m_py, trace)


def test_scan_parity_default_policy_high_pressure():
    """Saturation regime: rejections + scheduler preemptions dominate."""
    trace = trace_from_workload(
        WorkloadSpec(
            arrival_rate_per_s=1 / 6.0,
            flavors=[(f"f{i}", s) for i, s in enumerate(SIZES)],
            preemptible_fraction=0.5,
        ),
        4000.0, seed=11,
    )
    assert trace.n_events >= 300
    sim, dev, m_py = _run_both(trace, SchedulerPolicy(), n_hosts=8, seed=11)
    assert m_py.failures_normal + m_py.failures_preemptible > 0
    assert m_py.preemptions > 0
    _assert_bitwise_equal(sim, dev, m_py, trace)


def test_scan_parity_storm_only_and_empty_zone():
    """Storms against both a populated and an EMPTY zone (counts a storm,
    kills nobody) stay exact, including the zone churn accumulators."""
    trace = trace_from_workload(
        _workload(frac=1.0), 3000.0, seed=4,
        storms=((100.0, 2, 0.7), (1500.0, 0, 0.5), (2500.0, 1, 1.0)),
    )
    sim, dev, m_py = _run_both(trace, SchedulerPolicy(), n_hosts=9, seed=4)
    assert m_py.storms == 3
    _assert_bitwise_equal(sim, dev, m_py, trace)


def test_scan_parity_failure_heal_cycle():
    trace = trace_from_workload(
        _workload(), 5000.0, seed=9,
        failures=((1200.0, 1, 600.0), (2400.0, 3, None), (3000.0, 0, 300.0)),
        checkpoint_every=2,
    )
    sim, dev, m_py = _run_both(trace, SchedulerPolicy(), n_hosts=10, seed=9)
    _assert_bitwise_equal(sim, dev, m_py, trace)


def test_scan_parity_sample_cadence():
    """Sample-point semantics match at a non-default cadence (sample rows
    interleave differently with flush boundaries)."""
    trace = _rich_trace(2, duration=4000.0)
    policy = MIXED_POLICY
    sim = SoASimulator(_hosts(16), _workload(), seed=2, k_slots=K,
                       policy=policy)
    state0 = _snapshot(sim.fleet.state)
    m_py = sim.run_trace(trace, sample_every_s=170.0)
    dev = simulate_scan(trace, policy, state0, sample_every_s=170.0)
    m_dev = dev.sim_metrics(sim.fleet._cap0_total)
    assert m_py.t == m_dev.t
    assert m_py.utilization == m_dev.utilization
    assert m_py.utilization_normal == m_dev.utilization_normal


# ---------------------------------------------------------------------------
# 2. trace round-trip + malformed-trace rejection
# ---------------------------------------------------------------------------
def _random_events(rng, n: int):
    events, arrivals = [], []
    t = 0.0
    for _ in range(n):
        t += float(rng.integers(0, 30))
        k = rng.choice(["arrival", "departure", "fail_host", "heal_host",
                        "checkpoint", "zone_storm", "pad"])
        if k == "arrival":
            ev = TraceEvent(
                kind=k, time=t,
                res=tuple(float(v) for v in rng.integers(1, 8, size=3)),
                preemptible=bool(rng.random() < 0.5),
                duration=float(rng.integers(60, 600)),
                cost_kind=int(rng.integers(-1, 4)),
                period=float(rng.choice([-1.0, 60.0, 3600.0])),
                price=float(rng.integers(1, 5)),
                priority=int(rng.integers(-1, 3)),
                domain=int(rng.integers(-1, 2)),
            )
            arrivals.append(len(events))
        elif k in ("departure", "checkpoint") and arrivals:
            ev = TraceEvent(kind=k, time=t,
                            inst_id=int(rng.choice(arrivals)))
        elif k == "fail_host" or k == "heal_host":
            ev = TraceEvent(kind=k, time=t, host=int(rng.integers(0, 8)))
        elif k == "zone_storm":
            ev = TraceEvent(kind=k, time=t, zone=int(rng.integers(0, 3)),
                            frac=float(rng.uniform(0.1, 1.0)))
        else:
            ev = TraceEvent(kind="pad", time=t)
        events.append(ev)
    return events


@pytest.mark.parametrize("seed", PARITY_SEEDS)
def test_trace_round_trip_identity(seed):
    rng = np.random.default_rng(seed)
    events = _random_events(rng, 120)
    trace = EventTrace.from_events(events, n_dims=3)
    back = EventTrace.from_events(trace.events(), n_dims=3)
    for f in dataclasses.fields(EventTrace):
        assert np.array_equal(getattr(trace, f.name), getattr(back, f.name)), (
            f"round-trip diverged on column {f.name}"
        )


def test_workload_trace_round_trips_too():
    trace = _rich_trace(1, duration=2000.0)
    back = EventTrace.from_events(trace.events(), n_dims=trace.n_dims)
    for f in dataclasses.fields(EventTrace):
        assert np.array_equal(getattr(trace, f.name), getattr(back, f.name))


def test_malformed_unsorted_times_rejected():
    ok = EventTrace.from_events(
        [TraceEvent(kind="pad", time=10.0), TraceEvent(kind="pad", time=5.0)][:1],
        n_dims=2,
    )
    assert ok.n_events == 1
    with pytest.raises(ValueError, match=r"unsorted times: time\[1\]"):
        EventTrace.from_events(
            [TraceEvent(kind="pad", time=10.0),
             TraceEvent(kind="pad", time=5.0)],
            n_dims=2,
        )


def test_malformed_unknown_kind_rejected():
    with pytest.raises(ValueError, match="unknown event kind 'meteor'"):
        EventTrace.from_events([TraceEvent(kind="meteor", time=0.0)], n_dims=2)
    good = EventTrace.from_events([TraceEvent(kind="pad", time=0.0)], n_dims=2)
    bad_kind = np.array([99], np.int32)
    with pytest.raises(ValueError, match="unknown event kind 99 at row 0"):
        dataclasses.replace(good, kind=bad_kind)


def test_malformed_nan_payload_rejected():
    with pytest.raises(ValueError, match="NaN payload in column 'frac' at row 0"):
        EventTrace.from_events(
            [TraceEvent(kind="zone_storm", time=0.0, zone=0, frac=np.nan)],
            n_dims=2,
        )
    with pytest.raises(ValueError, match="NaN payload in column 'res' at row 0"):
        EventTrace.from_events(
            [TraceEvent(kind="arrival", time=0.0, res=(1.0, np.nan),
                        duration=60.0)],
            n_dims=2,
        )
    with pytest.raises(ValueError, match="non-finite arrival size at row 0"):
        EventTrace.from_events(
            [TraceEvent(kind="arrival", time=0.0, res=(1.0, np.inf),
                        duration=60.0)],
            n_dims=2,
        )
    with pytest.raises(ValueError, match="non-finite time at row 1"):
        EventTrace.from_events(
            [TraceEvent(kind="pad", time=0.0),
             TraceEvent(kind="pad", time=np.nan)],
            n_dims=2,
        )


def test_malformed_targets_rejected():
    with pytest.raises(ValueError, match="departure at row 0 targets"):
        EventTrace.from_events(
            [TraceEvent(kind="departure", time=0.0, inst_id=5)], n_dims=2
        )
    with pytest.raises(ValueError, match="checkpoint at row 0 targets"):
        EventTrace.from_events(
            [TraceEvent(kind="checkpoint", time=0.0, inst_id=-1)], n_dims=2
        )
    with pytest.raises(ValueError, match="precedes its arrival"):
        EventTrace.from_events(
            [TraceEvent(kind="departure", time=0.0, inst_id=1),
             TraceEvent(kind="arrival", time=5.0, res=(1.0, 1.0),
                        duration=60.0)],
            n_dims=2,
        )
    with pytest.raises(ValueError, match="kill fraction 1.5"):
        EventTrace.from_events(
            [TraceEvent(kind="zone_storm", time=0.0, zone=0, frac=1.5)],
            n_dims=2,
        )
    with pytest.raises(ValueError, match="fail_host at row 0 has no host"):
        EventTrace.from_events(
            [TraceEvent(kind="fail_host", time=0.0)], n_dims=2
        )


def test_trace_vs_fleet_validation():
    trace = EventTrace.from_events(
        [TraceEvent(kind="fail_host", time=0.0, host=99)], n_dims=3
    )
    sim = SoASimulator(_hosts(4), _workload(), seed=0, k_slots=K,
                       policy=SchedulerPolicy())
    with pytest.raises(ValueError, match="host index out of range"):
        simulate_scan(trace, SchedulerPolicy(), sim.fleet.state)
    kinds = EventTrace.from_events(
        [TraceEvent(kind="arrival", time=0.0, res=(1.0, 1.0, 1.0),
                    duration=60.0, cost_kind=COST_KINDS.index("revenue"))],
        n_dims=3,
    )
    with pytest.raises(ValueError, match="not in the\\s+policy's kind table"):
        simulate_scan(kinds, SchedulerPolicy(), sim.fleet.state)


# ---------------------------------------------------------------------------
# 3. ensemble determinism
# ---------------------------------------------------------------------------
def _lane_equal(a: ss.ScanResult, b: ss.ScanResult) -> None:
    assert a.counters == b.counters
    assert np.array_equal(a.host, b.host)
    assert np.array_equal(a.slot, b.slot)
    assert np.array_equal(a.ok, b.ok)
    assert np.array_equal(a.n_kill, b.n_kill)
    assert np.array_equal(a.sample_t, b.sample_t)
    assert np.array_equal(a.sample_free0, b.sample_free0)
    assert np.array_equal(a.sample_free0_normal, b.sample_free0_normal)
    for f in dataclasses.fields(a.state):
        assert np.array_equal(
            np.asarray(getattr(a.state, f.name)),
            np.asarray(getattr(b.state, f.name)),
        ), f"lane state column {f.name}"


def test_ensemble_equals_independent_runs():
    """32 seeds in ONE vmapped dispatch == 32 independent simulate_scan
    dispatches, element-wise bitwise (integer-cost regime)."""
    n_seeds = 32
    policy = SchedulerPolicy()
    sim = SoASimulator(_hosts(8), _workload(), seed=0, k_slots=K,
                       policy=policy)
    state0 = sim.fleet.state
    traces = [
        trace_from_workload(
            _workload(rate=1 / 40.0), 1500.0, seed=s,
            storms=((800.0, s % 3, 0.5),),
        )
        for s in range(n_seeds)
    ]
    # pad singles to one shared length so they share one compiled program
    emax = max(t.n_events for t in traces)
    padded = [t.padded(emax) for t in traces]
    singles = [simulate_scan(t, policy, state0) for t in padded]
    lanes = simulate_ensemble(traces, policy, state0)
    assert len(lanes) == n_seeds
    for single, lane, t in zip(singles, lanes, traces):
        e = t.n_events
        trimmed = dataclasses.replace(
            single, host=single.host[:e], slot=single.slot[:e],
            ok=single.ok[:e], n_kill=single.n_kill[:e],
        )
        _lane_equal(trimmed, lane)


def test_ensemble_bitwise_reproducible_across_dispatches():
    policy = SchedulerPolicy()
    sim = SoASimulator(_hosts(8), _workload(), seed=0, k_slots=K,
                       policy=policy)
    state0 = sim.fleet.state
    traces = [
        trace_from_workload(_workload(rate=1 / 50.0), 1200.0, seed=s)
        for s in range(8)
    ]
    first = simulate_ensemble(traces, policy, state0)
    second = simulate_ensemble(traces, policy, state0)
    for a, b in zip(first, second):
        _lane_equal(a, b)


def test_ensemble_multiplier_axis():
    """The stacked-policy-scalars axis: traced weigher multipliers ride a
    vmap lane each; a row equal to the static policy's multipliers is
    bitwise identical to the plain scan."""
    policy = SchedulerPolicy()  # weigher (1, 1, 0, 0), churn 0
    sim = SoASimulator(_hosts(8), _workload(), seed=0, k_slots=K,
                       policy=policy)
    state0 = sim.fleet.state
    trace = trace_from_workload(_workload(rate=1 / 30.0), 1500.0, seed=3)
    mults = np.array(
        [
            [1.0, 1.0, 0.0, 0.0, 0.0],   # == static row
            [4.0, 0.25, 0.0, 0.0, 0.0],
            [0.5, 2.0, 0.0, 0.0, 0.0],
        ],
        np.float32,
    )
    lanes = simulate_ensemble([trace], policy, state0, mults=mults)
    assert len(lanes) == 3
    plain = simulate_scan(trace, policy, state0)
    _lane_equal(plain, lanes[0])
    one = simulate_scan(trace, policy, state0, mult=mults[1])
    _lane_equal(one, lanes[1])


def test_ensemble_multiplier_validation():
    policy = SchedulerPolicy()
    sim = SoASimulator(_hosts(4), _workload(), seed=0, k_slots=K,
                       policy=policy)
    trace = trace_from_workload(_workload(rate=1 / 100.0), 500.0, seed=0)
    with pytest.raises(ValueError, match="column 2 must be 0"):
        simulate_ensemble([trace], policy, sim.fleet.state,
                          mults=np.array([[1.0, 1.0, 0.5, 0.0, 0.0]]))
    with pytest.raises(ValueError, match="keep the\\s+static multiplier's sign"):
        simulate_ensemble([trace], policy, sim.fleet.state,
                          mults=np.array([[1.0, -1.0, 0.0, 0.0, 0.0]]))
    with pytest.raises(ValueError, match="must have 5 entries"):
        simulate_ensemble([trace], policy, sim.fleet.state,
                          mults=np.array([[1.0, 1.0]]))


# ---------------------------------------------------------------------------
# 4. unsupported-plane guards
# ---------------------------------------------------------------------------
def test_unsupported_planes_raise():
    sim = SoASimulator(_hosts(4), _workload(), seed=0, k_slots=K,
                       policy=SchedulerPolicy())
    trace = trace_from_workload(_workload(rate=1 / 100.0), 400.0, seed=0)
    for bad in (
        SchedulerPolicy(relocate_threshold=0.5),
        SchedulerPolicy(adaptive_shortlist=True, shortlist=32),
    ):
        with pytest.raises(NotImplementedError,
                           match="which-planes-scan"):
            simulate_scan(trace, bad, sim.fleet.state)
    with pytest.raises(NotImplementedError):
        simulate_ensemble([trace], SchedulerPolicy(use_pallas=True),
                          sim.fleet.state)


# ---------------------------------------------------------------------------
# 5. streaming admission: in-scan queue vs the python front-end oracle
# ---------------------------------------------------------------------------
#: plain streaming policy — batch-full + SLO + capacity-freed drains
STREAM_POLICY = SchedulerPolicy(
    queue_capacity=16, admit_batch=4, slo_target_s=120.0, max_retries=2,
    n_classes=3,
)

#: every admission knob live at once: aging, degradation, mixed billing
STREAM_MIXED_POLICY = SchedulerPolicy(
    queue_capacity=16, admit_batch=4, slo_target_s=90.0, max_retries=2,
    n_classes=3, aging_rate=0.01, storm_threshold=0.05,
    cost_kind="period", cost_kinds=("count", "revenue", "recompute"),
)

_ADM_KEYS = ("arrivals", "admitted", "rejected_overflow", "rejected_retry",
             "drains", "retries", "degraded", "attempts", "fallbacks")


def _assert_stream_equal(py_sim: SoASimulator, dev: ss.ScanResult) -> None:
    """Admission-plane parity: counters, queue arrays, latency samples."""
    front = py_sim.fleet.admission
    st = front.stats
    expected = {k: getattr(st, k) for k in _ADM_KEYS}
    expected["queue_depth"] = front.waiting
    assert dev.admission == expected, (
        f"admission counters diverged: {dev.admission} vs {expected}"
    )
    # conservation: every arrival is admitted, rejected, or still queued
    adm = dev.admission
    assert adm["arrivals"] == (
        adm["admitted"] + adm["rejected_overflow"] + adm["rejected_retry"]
        + adm["queue_depth"]
    )
    # every attempt is exactly one outcome
    assert adm["attempts"] == (
        adm["admitted"] + adm["retries"] + adm["rejected_retry"]
    )
    # final queue arrays, every column bitwise
    for f in dataclasses.fields(front.qstate):
        a = np.asarray(getattr(front.qstate, f.name))
        b = np.asarray(getattr(dev.queue, f.name))
        assert np.array_equal(a, b), f"queue column {f.name} diverged"
    # sim-time wait distribution: the per-placement f32 differences are the
    # same multiset, and both percentile readers agree bit-for-bit
    dev_w = np.sort(dev.wait_s[dev.wait_s >= 0])
    py_w = np.sort(np.asarray(st.wait_s, np.float32))
    assert np.array_equal(dev_w, py_w), "wait_s distributions diverged"
    assert dev.wait_percentiles() == front.wait_percentiles()


def _run_both_streaming(trace: EventTrace, policy: SchedulerPolicy,
                        n_hosts: int, seed: int = 0):
    sim, dev, m_py = _run_both(trace, policy, n_hosts, seed)
    _assert_bitwise_equal(sim, dev, m_py, trace)
    _assert_stream_equal(sim, dev)
    return sim, dev, m_py


@pytest.mark.parametrize("seed", PARITY_SEEDS)
def test_stream_parity_randomized_all_kinds(seed):
    """The headline streaming sweep: 400+-event randomized traces with
    storms-under-degradation, aging, mixed billing, failures + heals,
    checkpoints — scan vs python streaming oracle bit-exact."""
    trace = _rich_trace(seed)
    assert trace.n_events >= 300
    sim, dev, _ = _run_both_streaming(
        trace, STREAM_MIXED_POLICY, n_hosts=16, seed=seed
    )
    assert dev.admission["admitted"] > 0
    assert dev.admission["drains"] > 0


def test_stream_parity_overflow_and_retry_exhaustion():
    """Saturation on a 2-host fleet: persistent retries fill the queue so
    fresh arrivals overflow, and retry budgets exhaust."""
    policy = SchedulerPolicy(queue_capacity=8, admit_batch=4,
                             slo_target_s=60.0, max_retries=6, n_classes=2)
    trace = trace_from_workload(
        WorkloadSpec(
            arrival_rate_per_s=1 / 6.0,
            flavors=[(f"f{i}", s) for i, s in enumerate(SIZES)],
            preemptible_fraction=0.5,
        ),
        4000.0, seed=11, priorities=(-1, 0, 1),
    )
    assert trace.n_events >= 400
    _, dev, _ = _run_both_streaming(trace, policy, n_hosts=2, seed=11)
    assert dev.admission["rejected_overflow"] > 0
    assert dev.admission["rejected_retry"] > 0
    assert dev.admission["retries"] > 0


def test_stream_parity_slo_deadline_drains():
    """Sparse arrivals never fill a batch: every drain is SLO-deadline
    (or end-of-run) triggered."""
    policy = SchedulerPolicy(queue_capacity=32, admit_batch=16,
                             slo_target_s=25.0, max_retries=2)
    trace = trace_from_workload(_workload(rate=1 / 60.0), 6000.0, seed=7)
    _, dev, _ = _run_both_streaming(trace, policy, n_hosts=8, seed=7)
    assert dev.admission["admitted"] > 0
    # a batch of 16 never accumulates at this rate, yet drains fired
    # throughout the run, not only in the epilogue
    assert dev.admission["drains"] > dev.admission["admitted"] // 16 + 1


def test_stream_parity_storm_degradation():
    """A tight storm_threshold demotes preemptible attempts mid-storm; the
    degraded counter and the demoted placements stay exact."""
    policy = dataclasses.replace(STREAM_MIXED_POLICY, storm_threshold=0.001)
    trace = trace_from_workload(
        _workload(frac=1.0), 4000.0, seed=13,
        storms=((400.0, 0, 0.8), (1500.0, 1, 0.7), (2600.0, 2, 0.9)),
        priorities=(-1, 0, 1, 2),
        cost_kinds=(-1, 0, 1, 2, 3),
    )
    _, dev, _ = _run_both_streaming(trace, policy, n_hosts=9, seed=13)
    assert dev.admission["degraded"] > 0


def test_stream_knobs_neutral_identity():
    """A traced knob row equal to the static policy's values is bitwise
    identical to the untraced scan (floor(0*w)=0, inf threshold =
    constant-False predicate)."""
    policy = STREAM_POLICY
    sim = SoASimulator(_hosts(8), _workload(), seed=1, k_slots=K,
                       policy=policy)
    state0 = _snapshot(sim.fleet.state)
    trace = trace_from_workload(_workload(), 3000.0, seed=1,
                                priorities=(-1, 0, 1, 2))
    static = simulate_scan(trace, policy, state0)
    neutral = np.asarray(
        [policy.aging_rate, policy.slo_target_s,
         np.inf if policy.storm_threshold is None
         else policy.storm_threshold],
        np.float32,
    )
    knobbed = simulate_scan(trace, policy, state0, knobs=neutral)
    _lane_equal(static, knobbed)
    assert static.admission == knobbed.admission
    assert np.array_equal(static.wait_s, knobbed.wait_s)
    for f in dataclasses.fields(static.queue):
        assert np.array_equal(getattr(static.queue, f.name),
                              getattr(knobbed.queue, f.name))


def test_stream_knob_ensemble_lanes():
    """An admission-knob sweep in ONE dispatch == per-row single scans."""
    policy = STREAM_POLICY
    sim = SoASimulator(_hosts(8), _workload(), seed=1, k_slots=K,
                       policy=policy)
    state0 = _snapshot(sim.fleet.state)
    trace = trace_from_workload(_workload(), 3000.0, seed=1,
                                priorities=(-1, 0, 1, 2))
    knob_rows = np.asarray(
        [[0.0, 120.0, np.inf],
         [0.05, 30.0, 0.02],
         [0.2, 300.0, 1.0]],
        np.float32,
    )
    lanes = simulate_ensemble([trace], policy, state0, knobs=knob_rows)
    assert len(lanes) == 3
    for row, lane in zip(knob_rows, lanes):
        single = simulate_scan(trace, policy, state0, knobs=row)
        _lane_equal(single, lane)
        assert single.admission == lane.admission
        assert np.array_equal(single.wait_s, lane.wait_s)


def test_stream_ensemble_lanes_match_padded_singles():
    """Mixed-length streaming traces on the vmap axis: each lane equals a
    single scan of the SAME padded trace (PAD rows at t_last can fire
    extra SLO drains, so the comparison must share the padding)."""
    policy = STREAM_POLICY
    sim = SoASimulator(_hosts(6), _workload(), seed=0, k_slots=K,
                       policy=policy)
    state0 = _snapshot(sim.fleet.state)
    traces = [
        trace_from_workload(_workload(rate=1 / 30.0), 1500.0, seed=s,
                            priorities=(-1, 0, 1, 2))
        for s in (1, 2, 3, 4)
    ]
    emax = max(t.n_events for t in traces)
    lanes = simulate_ensemble(traces, policy, state0)
    for t, lane in zip(traces, lanes):
        e = t.n_events
        single = simulate_scan(t.padded(emax), policy, state0)
        trimmed = dataclasses.replace(
            single, host=single.host[:e], slot=single.slot[:e],
            ok=single.ok[:e], n_kill=single.n_kill[:e],
        )
        _lane_equal(trimmed, lane)
        assert single.admission == lane.admission
        assert np.array_equal(single.wait_s[:e], lane.wait_s)
        for f in dataclasses.fields(single.queue):
            assert np.array_equal(getattr(single.queue, f.name),
                                  getattr(lane.queue, f.name))


def test_stream_knob_validation():
    sim = SoASimulator(_hosts(4), _workload(), seed=0, k_slots=K,
                       policy=STREAM_POLICY)
    state0 = _snapshot(sim.fleet.state)
    trace = trace_from_workload(_workload(rate=1 / 100.0), 400.0, seed=0)
    with pytest.raises(ValueError, match="queue_capacity > 0"):
        simulate_scan(trace, SchedulerPolicy(), state0,
                      knobs=np.array([0.0, 60.0, np.inf], np.float32))
    with pytest.raises(ValueError, match="knob rows must be"):
        simulate_scan(trace, STREAM_POLICY, state0,
                      knobs=np.array([0.0, 60.0], np.float32))
    with pytest.raises(ValueError, match="aging_rate knob"):
        simulate_scan(trace, STREAM_POLICY, state0,
                      knobs=np.array([-1.0, 60.0, np.inf], np.float32))
    with pytest.raises(ValueError, match="slo_target_s knob"):
        simulate_scan(trace, STREAM_POLICY, state0,
                      knobs=np.array([0.0, 0.0, np.inf], np.float32))
    with pytest.raises(ValueError, match="storm_threshold knob"):
        simulate_scan(trace, STREAM_POLICY, state0,
                      knobs=np.array([0.0, 60.0, np.nan], np.float32))
    with pytest.raises(ValueError, match="one knob row"):
        simulate_scan(trace, STREAM_POLICY, state0,
                      knobs=np.array([[0.0, 60.0, np.inf]], np.float32))
    with pytest.raises(ValueError, match="3 traces vs 2 knob rows"):
        simulate_ensemble([trace, trace, trace], STREAM_POLICY, state0,
                          knobs=np.full((2, 3), 60.0, np.float32))


def test_stream_trace_priority_validation():
    sim = SoASimulator(_hosts(4), _workload(), seed=0, k_slots=K,
                       policy=STREAM_POLICY)
    trace = trace_from_workload(_workload(rate=1 / 50.0), 800.0, seed=0,
                                priorities=(5,))
    with pytest.raises(ValueError, match="priority"):
        simulate_scan(trace, STREAM_POLICY, sim.fleet.state)
