"""Observability of the scheduler's own layers: the host spans of
``core/obs.py``, the device scopes (``jax.named_scope``) in the drain and
scan programs, and the admission counters and per-request wall samples of
``AdmissionStats`` — on both the front end and the scanned simulator.
"""
from __future__ import annotations

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import admission as adm
from repro.core import obs
from repro.core import scan_sim as ss
from repro.core.policy import SchedulerPolicy
from repro.core.scan_sim import trace_from_workload
from repro.core.simulator import SoASimulator, WorkloadSpec
from repro.core.soa_fleet import SoAFleet
from repro.core.types import VM_SPEC, Host, Request

CAP = VM_SPEC.make(vcpus=8, ram_mb=16000, disk_gb=160)
SIZES = [
    VM_SPEC.make(vcpus=1, ram_mb=2000, disk_gb=20),
    VM_SPEC.make(vcpus=2, ram_mb=4000, disk_gb=40),
    VM_SPEC.make(vcpus=4, ram_mb=8000, disk_gb=80),
]
K = 8
#: a shortlist below the fleet size, so stage 1, stage 2 and the fallback
#: are all in the program; small queue and few retries, so drains retry
#: and refuse
POLICY = SchedulerPolicy(queue_capacity=8, admit_batch=4, max_retries=3,
                         slo_target_s=60.0, shortlist=2)
SPANS = (obs.SUBMIT, obs.PACK, obs.DISPATCH, obs.FETCH, obs.MIRROR,
         obs.DEPART)
DRAIN_SCOPES = {"queue_push", "queue_select", "decide", "queue_pop",
                "stage1", "stage2", "fallback", "transition"}
SCAN_SCOPES = DRAIN_SCOPES | {"arrival", "departure", "drain", "epilogue"}


def _hosts(n: int):
    return [Host(name=f"h{i}", capacity=CAP, zone=f"z{i % 2}")
            for i in range(n)]


def _workload(rate: float, frac: float = 0.5) -> WorkloadSpec:
    return WorkloadSpec(
        arrival_rate_per_s=rate,
        flavors=[(f"f{i}", s) for i, s in enumerate(SIZES)],
        preemptible_fraction=frac,
    )


def _requests(rng, n: int):
    return [
        Request(id=f"r{i}", resources=SIZES[int(rng.integers(0, 3))],
                preemptible=bool(rng.random() < 0.5))
        for i in range(n)
    ]


def _scopes(lowered) -> set:
    """Every component of every op name in a lowered program."""
    text = lowered.as_text(debug_info=True)
    return {part for loc in re.findall(r'loc\("([^"]*)"', text)
            for part in loc.split("/")}


def test_host_spans_in_profiler_trace(tmp_path):
    """A small submit / drain / depart under the profiler writes every
    ``sched.*`` span into the host plane; the dispatch and the fetch of a
    drain carry the same ``drain`` id."""
    from jax.profiler import ProfileData

    fleet = SoAFleet(_hosts(4), k_slots=K, policy=POLICY)
    reqs = _requests(np.random.default_rng(0), 4)
    fleet.submit(reqs[0], 0.0)          # compiles outside the trace
    fleet.drain(0.0)
    with jax.profiler.trace(str(tmp_path)):
        for r in reqs[1:]:
            fleet.submit(r, 1.0)
        fleet.drain(1.0, block=False)
        results = fleet.admission.take_results()
        placed = [o.instance.id for d in results for o in d.outcomes]
        assert placed and fleet.depart(placed[0], now=2.0)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path[0])
    seen = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sched."):
                    seen.setdefault(ev.name, []).append(dict(ev.stats))
    assert set(seen) == set(SPANS)
    assert seen[obs.DISPATCH] == seen[obs.FETCH] == [{"drain": 1}]


def test_drain_program_carries_every_scope():
    fleet = SoAFleet(_hosts(8), k_slots=K, policy=POLICY)
    front = fleet.admission
    a, d = 4, len(fleet.spec.dims)
    lowered = adm._drain_kept.lower(
        fleet.state, front.qstate,
        np.zeros((a, d), np.float32), np.zeros(a, bool),
        np.full(a, -1, np.int32), np.full(a, -1, np.int32),
        np.full(a, -1.0, np.float32), np.full(a, -1, np.int32),
        np.zeros(a, np.int32), np.zeros(a, np.float32),
        np.ones(a, np.float32), np.zeros(a, bool), jnp.float32(0.0),
        policy=fleet._flush_policy(),
    )
    found = _scopes(lowered)
    assert DRAIN_SCOPES <= found, DRAIN_SCOPES - found
    assert "jit(_drain_entry)" in found


def test_scan_program_carries_every_scope():
    sim = SoASimulator(_hosts(8), _workload(1 / 20.0), seed=0, k_slots=K,
                       policy=POLICY)
    trace = trace_from_workload(_workload(1 / 20.0), 2000.0, seed=1)
    state = sim.fleet.state
    n, d = state.free_f.shape
    cols = {name: getattr(trace, name) for name in ss._COL_ORDER}
    lowered = ss._scan_fn(POLICY, False, False).lower(
        state, ss._device_cols(cols), jnp.zeros((n, d), jnp.float32),
        jnp.float32(300.0),
        jnp.zeros((len(POLICY.all_multipliers),), jnp.float32),
        jnp.zeros((3,), jnp.float32),
    )
    found = _scopes(lowered)
    assert SCAN_SCOPES <= found, SCAN_SCOPES - found
    assert "jit(run)" in found


@pytest.mark.parametrize("seed", (0, 1, 2))
def test_attempts_and_wall_samples(seed):
    """On every drain each attempt is exactly one outcome; every decided
    request has one wall sample whose segments fit inside its submit →
    absorbed time, and took 1 .. max_retries attempts (a refusal all)."""
    rng = np.random.default_rng(seed)
    fleet = SoAFleet(_hosts(2), k_slots=K, policy=POLICY)
    front = fleet.admission
    st = front.stats
    now = 0.0
    for req in _requests(rng, 60):
        now += float(rng.integers(1, 20))
        fleet.submit(req, now)
        if rng.random() < 0.4:
            fleet.drain(now, block=bool(rng.random() < 0.5))
            front.sync()
            assert st.attempts == st.admitted + st.retries + st.rejected_retry
    while front.waiting:
        fleet.drain(now + 1.0)
        assert st.attempts == st.admitted + st.retries + st.rejected_retry
    assert st.admitted and st.rejected_retry and st.retries
    n = st.admitted + st.rejected_retry
    for samples in (st.queue_wall_s, st.retry_wall_s, st.fetch_wall_s,
                    st.tries, st.refused):
        assert len(samples) == n
    assert sum(st.refused) == st.rejected_retry
    q, r, f = (np.asarray(x) for x in (st.queue_wall_s, st.retry_wall_s,
                                       st.fetch_wall_s))
    tries = np.asarray(st.tries)
    refused = np.asarray(st.refused)
    assert (q >= 0).all() and (r >= 0).all() and (f >= 0).all()
    assert ((tries >= 1) & (tries <= POLICY.max_retries)).all()
    assert (tries[refused] == POLICY.max_retries).all()
    assert (r[tries == 1] == 0).all()
    # placements line up with wall_wait_s; the segments telescope to it
    # (1e-9 s covers the rounding of three differences of clock stamps)
    wall = np.asarray(st.wall_wait_s)
    total = (q + r + f)[~refused]
    assert total.shape == wall.shape
    assert (total <= wall + 1e-9).all()


def test_scan_counters_match_front_end():
    """``attempts`` and ``fallbacks`` of the scanned simulator equal the
    front end's on a shared trace that retries, refuses and falls back."""
    trace = trace_from_workload(_workload(1 / 20.0, frac=0.8), 3000.0,
                                seed=5, priorities=(-1, 0, 1))
    sim = SoASimulator(_hosts(12), _workload(1 / 20.0), seed=5, k_slots=K,
                       policy=POLICY)
    state0 = jax.tree_util.tree_map(
        lambda x: jnp.asarray(np.asarray(x)), sim.fleet.state)
    sim.run_trace(trace)
    dev = ss.simulate_scan(trace, POLICY, state0)
    st = sim.fleet.admission.stats
    assert st.fallbacks > 0 and st.retries > 0 and st.rejected_retry > 0
    assert dev.admission["attempts"] == st.attempts
    assert dev.admission["fallbacks"] == st.fallbacks
    assert st.attempts == st.admitted + st.retries + st.rejected_retry
