"""Queue invariants + drained-queue parity for the streaming admission plane.

Property tests (hypothesis when installed, seeded sweeps otherwise — the
suite itself never skips, it is gated fail-on-skip in CI):

* **conservation** — every arrival lands in exactly one bucket:
  admitted + rejected (overflow / retries) + still queued + still pending;
* **FIFO-within-class** — admitted order within a priority class is the
  submission order of that class (and ``queue_select`` returns exactly the
  ``(class, seq)``-lexicographic top-B against a python model queue);
* **priority preemption only evicts lower classes** — every eviction victim
  is preemptible and of a strictly lower-priority class than the evictor;
* **drained-queue bit-exactness** — replaying each drain's attempt sequence
  through the rebuild-from-python oracle (``build_fleet_state`` +
  ``schedule_step``, and ``JaxPreemptibleScheduler`` at the decision level)
  reproduces every decision bit-for-bit, and the fleet state after each
  drain equals the oracle rebuild.

Event times, resources and prices are integer-valued so f32 arithmetic is
exact and equality can be strict (same regime as tests/test_soa_incremental).
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.core.admission import queue_init, queue_pop, queue_push, queue_select
from repro.core.jax_scheduler import (
    JaxPreemptibleScheduler,
    build_fleet_state,
    schedule_step,
)
from repro.core.policy import SchedulerPolicy
from repro.core.simulator import SoASimulator, WorkloadSpec
from repro.core.soa_fleet import SoAFleet
from repro.core.types import VM_SPEC, Host, Instance, Request

try:  # hypothesis is optional: fall back to a seeded sweep, never skip
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def seeded_property(n_fallback: int = 10, max_examples: int = 20):
    """Run a ``fn(seed)`` property via hypothesis when available, else over
    ``range(n_fallback)`` fixed seeds."""
    if HAVE_HYPOTHESIS:
        def deco(fn):
            return settings(max_examples=max_examples, deadline=None)(
                given(seed=st.integers(min_value=0, max_value=2**31 - 1))(fn)
            )
        return deco
    return pytest.mark.parametrize("seed", range(n_fallback))


CAP = VM_SPEC.make(vcpus=8, ram_mb=16000, disk_gb=160)
SIZES = [
    VM_SPEC.make(vcpus=1, ram_mb=2000, disk_gb=20),
    VM_SPEC.make(vcpus=2, ram_mb=4000, disk_gb=40),
    VM_SPEC.make(vcpus=4, ram_mb=8000, disk_gb=80),
]
K = 8


def _hosts(n):
    return [Host(name=f"h{i}", capacity=CAP) for i in range(n)]


def _stream(rng, n, n_classes=2, explicit_priority=False):
    """Random request stream; class derives from preemptible unless
    ``explicit_priority`` assigns one uniformly."""
    reqs = []
    for i in range(n):
        pre = bool(rng.random() < 0.5)
        prio = None
        if explicit_priority:
            prio = int(rng.integers(n_classes))
            # interactive classes must ride the preemption machinery: only
            # the lowest class is preemptible (the batch tier)
            pre = prio == n_classes - 1
        reqs.append(
            Request(
                id=f"r{i}", resources=SIZES[int(rng.integers(3))],
                preemptible=pre, priority=prio,
            )
        )
    return reqs


def _klass(req, n_classes=2):
    if req.priority is not None:
        return req.priority
    return 0 if not req.preemptible else n_classes - 1


# ---------------------------------------------------------------------------
# Pure-transition level: push/select/pop vs a python model queue
# ---------------------------------------------------------------------------


@seeded_property()
def test_queue_select_is_lexicographic_top_b(seed):
    rng = np.random.default_rng(seed)
    cap, batch, d = 16, 4, 3
    q = queue_init(cap, d)
    model = {}  # slot -> (klass, seq)
    next_seq = 0
    for _ in range(40):
        if rng.random() < 0.7 and len(model) < cap:  # push
            klass = int(rng.integers(3))
            q, slot, ok = queue_push(
                q, np.ones((d,), np.float32), False, -1, -1, -1.0, -1, klass,
                float(next_seq), 1.0,
            )
            assert bool(ok)
            model[int(slot)] = (klass, next_seq)
            next_seq += 1
        # select must equal the model's (class, seq)-sorted head
        idx, take = queue_select(q, batch)
        idx, take = np.asarray(idx), np.asarray(take)
        want = sorted(model.items(), key=lambda kv: kv[1])[:batch]
        got = [int(idx[j]) for j in range(batch) if take[j]]
        assert got == [slot for slot, _ in want]
        if got and rng.random() < 0.4:  # pop some of the selected rows
            b = len(got)
            takev = np.zeros((batch,), bool)
            takev[:b] = True
            placed = np.asarray(rng.random(batch) < 0.5) & takev
            q, dropped = queue_pop(
                q, np.asarray(idx, np.int32), takev, placed, max_retries=2
            )
            dropped = np.asarray(dropped)
            for j in range(b):
                if placed[j] or dropped[j]:
                    del model[int(idx[j])]


def test_queue_push_overflow_rejects_not_displaces():
    q = queue_init(2, 1)
    for i in range(2):
        q, _, ok = queue_push(q, np.zeros((1,), np.float32), False, -1, -1,
                              -1.0, -1, 0, float(i), 1.0)
        assert bool(ok)
    before = np.asarray(q.seq).copy()
    q, _, ok = queue_push(q, np.zeros((1,), np.float32), False, -1, -1,
                          -1.0, -1, 0, 99.0, 1.0)
    assert not bool(ok)  # full queue rejects the arrival…
    np.testing.assert_array_equal(np.asarray(q.seq), before)  # …untouched


# ---------------------------------------------------------------------------
# Conservation: admitted + rejected + queued + pending == arrivals
# ---------------------------------------------------------------------------


@seeded_property()
def test_conservation(seed):
    rng = np.random.default_rng(seed)
    # tiny queue + tiny fleet + few retries exercises every bucket:
    # overflow rejections, retry rejections, placements, leftovers
    policy = SchedulerPolicy(queue_capacity=8, admit_batch=4, max_retries=2)
    fleet = SoAFleet(_hosts(3), k_slots=K, policy=policy)
    front = fleet.admission
    now = 0.0
    for i, req in enumerate(_stream(rng, 40)):
        now += float(rng.integers(1, 30))
        fleet.submit(req, now)
        if rng.random() < 0.4:
            fleet.drain(now)
        st_ = front.stats
        assert st_.arrivals == (
            st_.admitted + st_.rejected + st_.queue_depth + front.pending
        ), f"conservation broken at arrival {i}"
    fleet.drain_all(now + 1.0)
    st_ = front.stats
    assert front.waiting == 0 or st_.queue_depth > 0  # drain_all converged
    assert st_.arrivals == st_.admitted + st_.rejected + st_.queue_depth
    assert st_.arrivals == 40


# ---------------------------------------------------------------------------
# FIFO within a class / strict priority between classes
# ---------------------------------------------------------------------------


@seeded_property()
def test_fifo_within_class_admission_order(seed):
    rng = np.random.default_rng(seed)
    # ample fleet + queue: every request admits, so the admitted order per
    # class must BE the submission order of that class
    policy = SchedulerPolicy(queue_capacity=128, admit_batch=8, n_classes=3)
    fleet = SoAFleet(_hosts(32), k_slots=K, policy=policy)
    reqs = _stream(rng, 48, n_classes=3, explicit_priority=True)
    now, admitted = 0.0, []
    for i, req in enumerate(reqs):
        now += 1.0
        fleet.submit(req, now)
        if (i + 1) % int(rng.integers(3, 10)) == 0:
            dr = fleet.drain(now)
            admitted += [o.request for o in dr.outcomes]
    for dr in fleet.drain_all(now + 1.0):
        admitted += [o.request for o in dr.outcomes]
    assert len(admitted) == len(reqs)
    for klass in range(3):
        submitted_k = [r.id for r in reqs if _klass(r, 3) == klass]
        admitted_k = [r.id for r in admitted if _klass(r, 3) == klass]
        assert admitted_k == submitted_k, f"class {klass} broke FIFO"


@seeded_property(n_fallback=6, max_examples=10)
def test_higher_class_always_drains_first(seed):
    rng = np.random.default_rng(seed)
    policy = SchedulerPolicy(queue_capacity=64, admit_batch=4, n_classes=2)
    fleet = SoAFleet(_hosts(16), k_slots=K, policy=policy)
    reqs = _stream(rng, 24)
    for i, req in enumerate(reqs):
        fleet.submit(req, float(i + 1))
    # every drain's attempts must be class-sorted, and no batch entry may be
    # attempted while an older interactive entry still waits
    waiting = {r.id: _klass(r) for r in reqs}
    now = 100.0
    for dr in fleet.drain_all(now):
        classes = [_klass(r) for r, _ in dr.attempts]
        assert classes == sorted(classes), "drain not in priority order"
        if dr.attempts and _klass(dr.attempts[0][0]) == 1:
            assert not any(k == 0 for k in waiting.values())
        for r, _ in dr.attempts:
            waiting.pop(r.id, None)
        for r in dr.rejected:
            waiting.pop(r.id, None)


# ---------------------------------------------------------------------------
# Priority preemption: evictions only ever hit strictly lower classes
# ---------------------------------------------------------------------------


@seeded_property()
def test_preemption_only_evicts_lower_classes(seed):
    rng = np.random.default_rng(seed)
    # small saturated fleet so interactive arrivals must evict batch work
    policy = SchedulerPolicy(queue_capacity=64, admit_batch=8)
    fleet = SoAFleet(_hosts(3), k_slots=K, policy=policy)
    reqs = _stream(rng, 60)
    klass_of = {r.id: _klass(r) for r in reqs}
    now, evictions = 0.0, 0
    for i, req in enumerate(reqs):
        now += float(rng.integers(1, 20))
        fleet.submit(req, now)
        if (i + 1) % 6 == 0:
            for dr in [fleet.drain(now)]:
                for out in dr.outcomes:
                    for victim in out.victims:
                        evictions += 1
                        assert victim.preemptible, "evicted a normal instance"
                        vid = victim.id.split("-", 1)[1]
                        assert klass_of[out.request.id] < klass_of[vid], (
                            "eviction across equal/higher class"
                        )
    assert evictions > 0, "workload never exercised preemption"


def test_interactive_preempts_batch_composition():
    """The ordering half (queue) composes with the paper's eviction half
    (decision pipeline): batch work fills the fleet, then one interactive
    arrival drains first AND evicts batch instances to fit."""
    big = VM_SPEC.make(vcpus=6, ram_mb=12000, disk_gb=120)
    policy = SchedulerPolicy(queue_capacity=16, admit_batch=4)
    fleet = SoAFleet(_hosts(1), k_slots=K, policy=policy)
    for i in range(4):  # 4×2 vcpus of batch work on an 8-vcpu host
        fleet.submit(Request(id=f"b{i}", resources=SIZES[1], preemptible=True),
                     now=float(i + 1))
    dr = fleet.drain(10.0)
    assert len(dr.outcomes) == 4
    fleet.submit(Request(id="interactive", resources=big), now=11.0)
    fleet.submit(Request(id="b-late", resources=SIZES[1], preemptible=True),
                 now=11.0)
    dr = fleet.drain(12.0)
    # interactive drains before the later batch arrival and evicts batch work
    assert dr.attempts[0][0].id == "interactive"
    out = dr.outcomes[0]
    assert out.request.id == "interactive" and len(out.victims) >= 2
    assert all(v.preemptible for v in out.victims)


# ---------------------------------------------------------------------------
# Backfill retries
# ---------------------------------------------------------------------------


def test_backfill_retry_then_placement_after_capacity_frees():
    policy = SchedulerPolicy(queue_capacity=8, admit_batch=2, max_retries=8)
    fleet = SoAFleet(_hosts(1), k_slots=K, policy=policy)
    blocker = fleet.schedule_request(
        Request(id="blocker", resources=CAP), now=1.0
    )
    assert blocker.ok
    fleet.submit(Request(id="waiter", resources=SIZES[0]), now=2.0)
    dr = fleet.drain(3.0)
    assert dr.outcomes == () and [r.id for r in dr.retried] == ["waiter"]
    assert fleet.admission.stats.retries == 1
    fleet.depart(blocker.instance.id)  # capacity frees → backfill succeeds
    dr = fleet.drain(4.0)
    assert [o.request.id for o in dr.outcomes] == ["waiter"]


def test_retry_exhaustion_rejects():
    policy = SchedulerPolicy(queue_capacity=8, admit_batch=2, max_retries=3)
    fleet = SoAFleet(_hosts(1), k_slots=K, policy=policy)
    assert fleet.schedule_request(
        Request(id="blocker", resources=CAP), now=1.0
    ).ok
    fleet.submit(Request(id="doomed", resources=SIZES[0]), now=2.0)
    for t in (3.0, 4.0):
        dr = fleet.drain(t)
        assert [r.id for r in dr.retried] == ["doomed"]
    dr = fleet.drain(5.0)  # third (= max_retries) attempt drops it
    assert [r.id for r in dr.rejected] == ["doomed"]
    assert fleet.admission.stats.rejected_retry == 1
    assert fleet.drain(6.0).attempts == ()  # queue is empty now


def test_queue_overflow_rejects_at_drain():
    policy = SchedulerPolicy(queue_capacity=4, admit_batch=4, max_retries=1)
    fleet = SoAFleet(_hosts(1), k_slots=K, policy=policy)
    assert fleet.schedule_request(
        Request(id="blocker", resources=CAP), now=1.0
    ).ok
    for i in range(7):  # 7 arrivals into a 4-slot queue
        fleet.submit(Request(id=f"r{i}", resources=SIZES[0]), now=2.0)
    dr = fleet.drain(3.0)
    # 4 queued (then dropped: max_retries=1 and the host is full), 3 overflow
    assert fleet.admission.stats.rejected_overflow == 3
    assert fleet.admission.stats.rejected_retry == 4
    assert len(dr.rejected) == 7


# ---------------------------------------------------------------------------
# Drained-queue decisions are bit-exact vs the unqueued oracle
# ---------------------------------------------------------------------------


def _assert_states_equal(state, oracle, msg=""):
    valid = np.asarray(state.inst_valid)
    np.testing.assert_array_equal(valid, np.asarray(oracle.inst_valid), err_msg=msg)
    for field in ("free_f", "free_n", "schedulable", "domain", "slow"):
        np.testing.assert_array_equal(
            np.asarray(getattr(state, field)),
            np.asarray(getattr(oracle, field)),
            err_msg=f"{msg}: {field}",
        )
    for field in ("inst_start", "inst_price", "inst_ckpt", "inst_cost_kind"):
        np.testing.assert_array_equal(
            np.asarray(getattr(state, field)) * valid,
            np.asarray(getattr(oracle, field)) * valid,
            err_msg=f"{msg}: {field}",
        )
    np.testing.assert_array_equal(
        np.asarray(state.inst_res) * valid[..., None],
        np.asarray(oracle.inst_res) * valid[..., None],
        err_msg=f"{msg}: inst_res",
    )


class _PyMirror:
    def __init__(self, hosts):
        self.hosts = hosts
        self.by_name = {h.name: h for h in hosts}

    def apply(self, outcome):
        host = self.by_name[outcome.host]
        for victim in outcome.victims:
            host.remove(victim.id)
        host.place(
            Instance(
                id=outcome.instance.id,
                resources=outcome.instance.resources,
                preemptible=outcome.instance.preemptible,
                host=host.name,
                start_time=outcome.instance.start_time,
                price_rate=outcome.instance.price_rate,
                cost_kind=outcome.instance.cost_kind,
            )
        )


@seeded_property(n_fallback=4, max_examples=8)
def test_drained_queue_bit_exact_vs_oracle(seed):
    """Replay every drain's attempt sequence against (a) ``schedule_step``
    on the rebuilt-from-python state and (b) the ``JaxPreemptibleScheduler``
    rebuild oracle; decisions must match bit-for-bit and the fleet state
    after each drain must equal the oracle rebuild."""
    rng = np.random.default_rng(seed)
    hosts = _hosts(12)
    py = _PyMirror(hosts)
    policy = SchedulerPolicy(queue_capacity=32, admit_batch=4)
    # k_slots > capacity/min-size: a host can never run out of free slots,
    # so the drain path (require_free_slot=True) and the rebuild oracle
    # (require_free_slot=False) face identical feasibility everywhere
    k = 12
    fleet = SoAFleet(hosts, k_slots=k, policy=policy)
    oracle = JaxPreemptibleScheduler(k_slots=k, policy=policy)
    reqs = _stream(rng, 36)
    now = 0.0
    for i, req in enumerate(reqs):
        now += float(rng.integers(1, 60))
        fleet.submit(req, now)
        if (i + 1) % int(rng.integers(2, 7)) != 0:
            continue
        dr = fleet.drain(now)
        outs = iter(dr.outcomes)
        for areq, placed in dr.attempts:
            # (a) one step on the oracle state rebuilt from the mirror
            ostate, _ = build_fleet_state(
                py.hosts, k_slots=k, domain_ids=fleet.domain_ids,
                slot_assignment=fleet.slot_assignment(),
            )
            res, pre, dom, kind, period, _excl = fleet._req_arrays(areq)
            _, (oh, oslot, ook, okill, _fb, _mg) = schedule_step(
                ostate, res, pre, dom, dr.now, 1.0,
                policy=policy, req_cost_kind=kind, req_period=period,
                donate=False,
            )
            assert bool(ook) == placed, f"oracle ok mismatch for {areq.id}"
            # (b) the rebuild-per-call scheduler agrees at decision level
            sched = oracle.schedule(areq, py.hosts, dr.now)
            assert sched.ok == placed, f"rebuild oracle mismatch {areq.id}"
            if not placed:
                continue
            out = next(outs)
            assert out.host == fleet.names[int(oh)] == sched.host
            assert set(sched.plan.ids) == {v.id for v in out.victims}
            py.apply(out)
        # state parity after the whole drain
        ostate, _ = build_fleet_state(
            py.hosts, k_slots=k, domain_ids=fleet.domain_ids,
            slot_assignment=fleet.slot_assignment(),
        )
        _assert_states_equal(fleet.state, ostate, msg=f"after drain @{now}")


# ---------------------------------------------------------------------------
# Double-buffered (non-blocking) dispatch delivers identical results
# ---------------------------------------------------------------------------


def test_nonblocking_drains_match_blocking():
    def run(block):
        policy = SchedulerPolicy(queue_capacity=32, admit_batch=4)
        fleet = SoAFleet(_hosts(4), k_slots=K, policy=policy)
        rng = np.random.default_rng(123)
        results = []  # blocking drains return directly; async ones bank
        for i, req in enumerate(_stream(rng, 24)):
            fleet.submit(req, float(i + 1))
            if (i + 1) % 4 == 0:
                dr = fleet.drain(float(i + 1), block=block)
                if dr is not None:
                    results.append(dr)
        dr = fleet.drain(100.0, block=block)
        if dr is not None:
            results.append(dr)
        results += fleet.admission.take_results()
        placed = [
            (o.request.id, o.host) for dr in results for o in dr.outcomes
        ]
        st_ = fleet.admission.stats
        return placed, (st_.admitted, st_.rejected, st_.queue_depth)

    assert run(block=True) == run(block=False)


# ---------------------------------------------------------------------------
# Streaming simulator mode
# ---------------------------------------------------------------------------


def _streaming_sim(seed=11):
    medium = VM_SPEC.make(vcpus=2, ram_mb=4000, disk_gb=40)
    spec = WorkloadSpec(
        arrival_rate_per_s=1 / 20.0,
        preemptible_fraction=0.5,
        flavors=(("medium", medium),),
    )
    policy = SchedulerPolicy(
        queue_capacity=64, admit_batch=8, slo_target_s=120.0
    )
    return SoASimulator(_hosts(16), spec, seed=seed, policy=policy)


def test_streaming_simulator_conserves_and_is_deterministic():
    runs = []
    for _ in range(2):
        sim = _streaming_sim()
        m = sim.run(12 * 3600.0, sample_every_s=900.0)
        st_ = sim.fleet.admission.stats
        assert st_.arrivals == st_.admitted + st_.rejected + st_.queue_depth
        assert st_.admitted == m.placed_normal + m.placed_preemptible
        assert st_.rejected == m.failures_normal + m.failures_preemptible
        assert st_.admitted > 50
        runs.append(
            (m.placed_normal, m.placed_preemptible, m.failures_normal,
             m.failures_preemptible, m.preemptions, tuple(m.utilization))
        )
    assert runs[0] == runs[1]


def test_streaming_simulator_respects_slo_deadline():
    """With a lazy batch size, the SLO tick still forces timely drains: no
    placed request waits (in sim time) much past slo_target_s."""
    sim = _streaming_sim()
    sim.run(12 * 3600.0)
    st_ = sim.fleet.admission.stats
    slo = sim.fleet.policy.slo_target_s
    assert st_.wait_s, "nothing was admitted"
    # drains happen AT the deadline tick; waits may exceed the target only
    # by the retry/backfill path, never for first-attempt admissions
    waits = np.asarray(st_.wait_s)
    assert float(np.percentile(waits, 50)) <= slo + 1e-6


# ---------------------------------------------------------------------------
# Packed-key drain order: one fused sort == the old two-pass lexsort
# ---------------------------------------------------------------------------


@seeded_property()
def test_queue_select_packed_key_matches_lexsort(seed):
    """``queue_select`` now sorts ONE packed uint32 key; it must reproduce
    the two-key ``lexsort((seq, effective_klass))`` order bit-exactly —
    including aged and retried entries — at several class counts/batches."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    for n_classes, batch in ((2, 4), (3, 8), (8, 5), (255, 16), (None, 6)):
        nc = n_classes if n_classes else 255
        cap, d = 32, 3
        q = queue_init(cap, d)
        occupied = set()
        t = 0.0
        for i in range(64):
            t += float(rng.integers(0, 40))
            if rng.random() < 0.75 and len(occupied) < cap:
                q, slot, ok = queue_push(
                    q, np.ones((d,), np.float32), False, -1, -1, -1.0, -1,
                    int(rng.integers(nc)), t, 1.0,
                )
                assert bool(ok)
                occupied.add(int(slot))
            elif occupied:  # burn retries on a few random rows (tries += 1,
                # seq ticket KEPT) without ever dropping them
                rows = rng.choice(sorted(occupied), size=1)
                idxv = np.full((4,), rows[0], np.int32)
                takev = np.zeros((4,), bool)
                takev[0] = True
                q, dropped = queue_pop(
                    q, idxv, takev, np.zeros((4,), bool), max_retries=10**6
                )
                assert not np.asarray(dropped).any()
            aging = float(rng.choice([0.0, 0.002, 0.05]))
            now = jnp.float32(t)
            idx, take = queue_select(
                q, batch, now=now, aging_rate=aging, n_classes=n_classes
            )
            # reference: the pre-packing two-pass order
            klass = np.asarray(q.klass)
            if aging:
                waited = np.maximum(t - np.asarray(q.enq_t), 0.0)
                decay = np.floor(
                    np.float32(aging) * waited.astype(np.float32)
                ).astype(np.int32)
                klass = np.maximum(klass - decay, 0)
            valid = np.asarray(q.valid)
            eff = np.where(valid, klass, np.iinfo(np.int32).max)
            ref = np.asarray(
                jnp.lexsort((jnp.asarray(np.asarray(q.seq)),
                             jnp.asarray(eff)))
            )[:batch]
            # compare the VALID prefix (padding rows gather arbitrary
            # invalid entries; both sorts place them strictly last)
            idx, take = np.asarray(idx), np.asarray(take)
            assert np.array_equal(take, valid[ref]), (
                f"take mask diverged (n_classes={n_classes}, batch={batch})"
            )
            assert np.array_equal(idx[take], ref[valid[ref]]), (
                f"packed-key order diverged from lexsort "
                f"(n_classes={n_classes}, batch={batch}, aging={aging})"
            )


@pytest.mark.parametrize("aging", [0.0, 0.05])
def test_queue_select_take_is_live_prefix(aging):
    """The drains run rows ``[0, sum(take))`` only, so ``take`` must be a
    prefix of the batch holding ``min(depth, B)`` rows — at every depth
    0..Q, with valid rows scattered over the slots, mixed classes, retried
    entries, and aging off and on."""
    import jax.numpy as jnp

    rng = np.random.default_rng(int(aging * 1000) + 14)
    cap, batch, d, n_classes = 24, 8, 3, 3
    for depth in range(cap + 1):
        q = queue_init(cap, d)
        t = 0.0
        for _ in range(cap):  # fill, then empty random slots down to depth
            t += float(rng.integers(0, 30))
            q, _, ok = queue_push(
                q, np.ones((d,), np.float32), False, -1, -1, -1.0, -1,
                int(rng.integers(n_classes)), t, 1.0,
            )
            assert bool(ok)
        gone = rng.choice(cap, size=cap - depth, replace=False)
        stay = np.setdiff1d(np.arange(cap), gone)
        valid = np.zeros((cap,), bool)
        valid[stay] = True
        tries = np.where(valid, rng.integers(0, 3, size=cap), 0)
        q = dataclasses.replace(
            q, valid=jnp.asarray(valid), tries=jnp.asarray(tries, jnp.int32)
        )
        assert int(q.depth) == depth
        idx, take = queue_select(
            q, batch, now=jnp.float32(t + 100.0), aging_rate=aging,
            n_classes=n_classes,
        )
        idx, take = np.asarray(idx), np.asarray(take)
        n_live = min(depth, batch)
        assert int(take.sum()) == n_live, f"depth {depth}"
        assert np.array_equal(take, np.arange(batch) < n_live), (
            f"take is not a prefix at depth {depth}: {take}"
        )
        assert valid[idx[:n_live]].all()


def test_wait_percentile_readers_agree():
    """The front end's sim-time p50/p99 reader interpolates in f32 —
    bit-identical to ``ScanResult.wait_percentiles`` over the same waits."""
    sim = _streaming_sim()
    sim.run(6 * 3600.0)
    front = sim.fleet.admission
    pct = front.wait_percentiles()
    assert set(pct) == {"wait_p50_s", "wait_p99_s"}
    w = np.asarray(front.stats.wait_s, np.float32)
    assert pct["wait_p50_s"] == float(np.percentile(w, 50))
    assert pct["wait_p99_s"] == float(np.percentile(w, 99))
    assert pct["wait_p50_s"] <= pct["wait_p99_s"]
    # summary() exposes the same sim-time percentiles
    summ = front.stats.summary()
    assert summ["wait_p50_s"] == pct["wait_p50_s"]
    assert summ["wait_p99_s"] == pct["wait_p99_s"]
