"""The drains run their live rows only, and that is exact.

Both drains (``admission._drain_entry`` and the scan's in-carry drain) loop
over the taken rows ``[0, sum(take))`` of the selected batch
(``jax_scheduler._scan_live_rows``) instead of scanning all ``admit_batch``
rows, padding included.  The reference here is the full-length formulation
the drains used before: a ``lax.scan`` over every row, where a padded row
carries the ``PAD_RES`` sentinel and no-ops.  Swapped in for the helper, it
must give bitwise the same fleet state, queue state, counters and outputs
at every taken row, at live counts 0, 1, 5, 63 and 64 of a 64-row batch.

Resources, times and prices are integer-valued so every sum is exact in
float32 (the regime of tests/test_admission.py).
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core import admission as adm
from repro.core import scan_sim as ss
from repro.core.policy import SchedulerPolicy
from repro.core.scan_sim import (
    EventTrace,
    TraceEvent,
    simulate_ensemble,
    simulate_scan,
)
from repro.core.screen_math import POS_INF
from repro.core.soa_fleet import SoAFleet
from repro.core.types import VM_SPEC, Host

CAP = VM_SPEC.make(vcpus=8, ram_mb=16000, disk_gb=160)
SIZES = [
    VM_SPEC.make(vcpus=1, ram_mb=2000, disk_gb=20),
    VM_SPEC.make(vcpus=2, ram_mb=4000, disk_gb=40),
    VM_SPEC.make(vcpus=4, ram_mb=8000, disk_gb=80),
]
K = 8
B = 64
LIVE_COUNTS = (0, 1, 5, 63, 64)
SCAN_HOSTS = 16

#: a 64-row batch on an 8-host fleet: a full burst overfills it, so drains
#: preempt, fail, retry and drop (the scans run 16 hosts, where most of a
#: burst places)
POLICY = SchedulerPolicy(
    queue_capacity=128, admit_batch=B, slo_target_s=60.0, max_retries=3,
    n_classes=3,
)


def _padded_rows(body, carry, xs, take, dead):
    """The reference: every row of the batch through ``body``."""
    del take, dead
    return lax.scan(body, carry, xs)


def _clear_scan_caches():
    ss._scan_fn.cache_clear()
    ss._ensemble_fn.cache_clear()


@contextlib.contextmanager
def padded_drains():
    """Both drains as full-length scans while the block traces."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(adm, "_scan_live_rows", _padded_rows)
        mp.setattr(ss, "_scan_live_rows", _padded_rows)
        _clear_scan_caches()
        try:
            yield
        finally:
            _clear_scan_caches()


def _bits_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and (
        a.tobytes() == b.tobytes()
    )


def _assert_tree_bits(a, b, what: str) -> None:
    for f in dataclasses.fields(a):
        assert _bits_equal(getattr(a, f.name), getattr(b, f.name)), (
            f"{what} column {f.name} diverged"
        )


def _fleet(n_hosts: int = 8) -> SoAFleet:
    hosts = [Host(name=f"h{i}", capacity=CAP) for i in range(n_hosts)]
    return SoAFleet(hosts, k_slots=K, policy=POLICY)


def _copy(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(np.asarray(a)), tree)


# ---------------------------------------------------------------------------
# The served drain (``_drain_entry``)
# ---------------------------------------------------------------------------


def _arrivals(rng, n: int, t0: int):
    """A 64-row arrival buffer with ``n`` live rows (the rest padding)."""
    d = len(CAP.vec32)
    res = np.full((B, d), adm.PAD_RES, np.float32)
    pre = np.zeros((B,), bool)
    cls = np.zeros((B,), np.int32)
    enq = np.zeros((B,), np.float32)
    live = np.zeros((B,), bool)
    for i in range(n):
        res[i] = SIZES[int(rng.integers(3))].vec32
        pre[i] = bool(rng.random() < 0.5)
        cls[i] = 2 if pre[i] else int(rng.integers(2))
        enq[i] = float(t0 + i)
        live[i] = True
    return (
        res, pre, np.full((B,), -1, np.int32), np.full((B,), -1, np.int32),
        np.full((B,), -1.0, np.float32), np.full((B,), -1, np.int32), cls,
        enq, np.ones((B,), np.float32), live,
    )


def _reference_drain(*args, policy):
    return adm._drain_entry(*args, policy=policy)


_reference_drain_jit = jax.jit(_reference_drain, static_argnames=("policy",))

#: aux positions of the per-decision outputs and the values ``_drain_entry``
#: documents for a row it skips: host, slot, kill, fell_back, margin
_DEAD = {5: -1, 6: -1, 7: False, 8: False, 9: np.float32(POS_INF)}


@pytest.mark.parametrize("n_live", LIVE_COUNTS)
def test_drain_entry_skips_dead_rows_exactly(n_live):
    """A drain of ``n_live`` arrivals, then drains of the retries it leaves
    until one finds the queue empty, equal the padded reference drain bit
    for bit."""
    rng = np.random.default_rng(1400 + n_live)
    state = _fleet().state
    q = adm.queue_init(POLICY.queue_capacity, len(CAP.vec32))
    ref_state, ref_q = _copy(state), _copy(q)
    bufs = _arrivals(rng, n_live, 1)
    takes = []
    for round_ in range(POLICY.max_retries + 2):
        now = jnp.float32(100.0 * (round_ + 1))
        state, q, aux = adm._drain_kept(state, q, *bufs, now, policy=POLICY)
        with padded_drains():
            ref_state, ref_q, ref = _reference_drain_jit(
                ref_state, ref_q, *bufs, now, policy=POLICY
            )
        bufs = _arrivals(rng, 0, 0)
        _assert_tree_bits(state, ref_state, "fleet state")
        _assert_tree_bits(q, ref_q, "queue")
        take = np.asarray(aux[3])
        takes.append(int(take.sum()))
        assert np.array_equal(take, np.arange(B) < takes[-1])
        for i, (got, want) in enumerate(zip(aux, ref)):
            got, want = np.asarray(got), np.asarray(want)
            if i in _DEAD:
                assert _bits_equal(got[take], want[take]), f"output {i}"
                assert (got[~take] == _DEAD[i]).all(), f"dead output {i}"
            else:
                assert _bits_equal(got, want), f"output {i}"
        if not takes[-1]:
            break
    assert takes[0] == n_live
    assert takes[-1] == 0, f"queue never emptied: {takes}"


# ---------------------------------------------------------------------------
# The scan's drain (``simulate_scan``, streaming)
# ---------------------------------------------------------------------------


def _burst_trace(n_burst: int, seed: int) -> EventTrace:
    """``n_burst`` arrivals at t=10..., so the first drain holds exactly
    ``n_burst`` live rows: a full batch at the 64th arrival, else the SLO
    pre-drain at t=100.  Then arrivals and departures that free capacity
    while retries wait, and the epilogue."""
    rng = np.random.default_rng(seed)
    events = []

    def arrival(t):
        size = SIZES[int(rng.integers(3))]
        pre = bool(rng.random() < 0.5)
        events.append(TraceEvent(
            kind="arrival", time=float(t), res=tuple(size.vec32),
            preemptible=pre, duration=float(rng.integers(300, 900)),
            priority=2 if pre else int(rng.integers(2)),
        ))
        return len(events) - 1

    rows = [arrival(10) for _ in range(n_burst)]
    for t in (100, 150, 170):
        rows.append(arrival(t))
    for j, t in enumerate((300, 400, 500)):
        if j < len(rows):
            events.append(TraceEvent(
                kind="departure", time=float(t), inst_id=rows[j]
            ))
    for t in range(600, 900, 25):
        arrival(t)
    return EventTrace.from_events(events, len(CAP.vec32))


@pytest.fixture(scope="module")
def scan_runs():
    """Each burst's trace through the live-row scan and the padded one, all
    padded to one length so each formulation compiles once."""
    state0 = _fleet(SCAN_HOSTS).state
    traces = {n: _burst_trace(n, 1400 + n) for n in LIVE_COUNTS}
    emax = max(t.n_events for t in traces.values())
    traces = {n: t.padded(emax) for n, t in traces.items()}
    live = {
        n: simulate_scan(t, POLICY, _copy(state0)) for n, t in traces.items()
    }
    with padded_drains():
        ref = {
            n: simulate_scan(t, POLICY, _copy(state0))
            for n, t in traces.items()
        }
    return live, ref


def _assert_scan_bits(a: ss.ScanResult, b: ss.ScanResult) -> None:
    _assert_tree_bits(a.state, b.state, "fleet state")
    _assert_tree_bits(a.queue, b.queue, "queue")
    for name in ("host", "slot", "ok", "n_kill", "sample_t", "sample_free0",
                 "sample_free0_normal", "wait_s"):
        assert _bits_equal(getattr(a, name), getattr(b, name)), name
    assert a.counters == b.counters
    assert a.admission == b.admission


@pytest.mark.parametrize("n_live", LIVE_COUNTS)
def test_scan_drain_skips_dead_rows_exactly(scan_runs, n_live):
    """A zero-row drain never fires in one scan (every trigger needs a
    waiting entry); the burst of 0 is then the trace's later events only,
    and the ensemble case below runs zero-row drains."""
    live, ref = scan_runs
    a, b = live[n_live], ref[n_live]
    _assert_scan_bits(a, b)
    adm_ = a.admission
    assert adm_["attempts"] >= n_live
    assert adm_["attempts"] == (
        adm_["admitted"] + adm_["retries"] + adm_["rejected_retry"]
    )
    if n_live == B:
        assert adm_["retries"] > 0 and adm_["rejected_retry"] > 0


def test_ensemble_lanes_with_different_live_counts(scan_runs):
    """Lanes whose drains hold 0, 1, 5, 63 and 64 live rows in one vmapped
    dispatch (the loop runs to the lanes' largest count, and a lane whose
    drain is the untaken side of a select runs it at its own count) equal
    the independent single runs."""
    live, _ = scan_runs
    state0 = _fleet(SCAN_HOSTS).state
    traces = [_burst_trace(n, 1400 + n) for n in LIVE_COUNTS]
    lanes = simulate_ensemble(traces, POLICY, state0)
    for n, t, lane in zip(LIVE_COUNTS, traces, lanes):
        single = live[n]
        e = t.n_events
        trimmed = dataclasses.replace(
            single, host=single.host[:e], slot=single.slot[:e],
            ok=single.ok[:e], n_kill=single.n_kill[:e],
            wait_s=single.wait_s[:e],
        )
        _assert_scan_bits(trimmed, lane)
