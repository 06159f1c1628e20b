"""Device-sharded screen parity: decisions taken with the fleet partitioned
host-major across a device mesh (``mesh=`` on ``schedule_decision`` /
``schedule_step`` / ``schedule_many`` / ``SoAFleet``) must be BIT-IDENTICAL
to the unsharded oracle — including fleets whose host count does not divide
the shard count (padding), fallback-triggering fleets (the ``lax.cond`` full
enumeration on sharded arrays), and mass-tied fleets where everything rides
on the cross-shard merge reproducing ``lax.top_k``'s tie ordering.

Run with forced host devices to exercise real sharding on CPU:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest tests/test_sharded_parity.py

CI's multi-device job does exactly that and treats any skip as a failure
(see .github/workflows/ci.yml); on a single-device run the shard_map cases
skip and only the pure-math merge tests run.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fleet_sharding import (
    fleet_mesh,
    merge_shortlists,
    pad_fleet_state,
    padded_hosts,
    shard_fleet_state,
)
from repro.core.jax_scheduler import (
    build_fleet_state,
    build_soa_state,
    schedule_decision,
    schedule_many,
    schedule_step,
)
from repro.core.cost import MixedCost, PeriodCost, RevenueCost
from repro.core.policy import SchedulerPolicy
from repro.core.screen_math import NEG_INF
from repro.core.soa_fleet import SoAFleet
from repro.core.types import VM_SPEC, Host, Instance, Request

NOW = 500_000.0
CAP = VM_SPEC.make(vcpus=8, ram_mb=16000, disk_gb=160)
SIZES = [
    VM_SPEC.make(vcpus=1, ram_mb=2000, disk_gb=20),
    VM_SPEC.make(vcpus=2, ram_mb=4000, disk_gb=40),
    VM_SPEC.make(vcpus=4, ram_mb=8000, disk_gb=80),
]

multi_device = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >1 device (XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


def _random_fleet(rng, n_hosts, fill=0.85, k_max=8):
    hosts = []
    iid = 0
    for i in range(n_hosts):
        h = Host(name=f"h{i}", capacity=CAP)
        while h.used().vec[0] < fill * CAP.vec[0]:
            size = SIZES[int(rng.integers(3))]
            if not size.fits_in(h.free_full):
                break
            pre = bool(rng.random() < 0.6) and len(h.preemptible_instances()) < k_max
            h.place(
                Instance(
                    id=f"x{iid}",
                    resources=size,
                    preemptible=pre,
                    host=h.name,
                    start_time=NOW - float(rng.integers(10, 500)) * 60.0,
                )
            )
            iid += 1
        hosts.append(h)
    return hosts


def _sharded_pair(hosts, m, k_slots=8):
    """(padded unsharded state, sharded state, mesh) for the full mesh."""
    mesh = fleet_mesh()
    state, _ = build_fleet_state(hosts, k_slots=k_slots)
    padded = pad_fleet_state(
        state, padded_hosts(len(hosts), mesh.size, m_keep=m + 1)
    )
    return padded, shard_fleet_state(padded, mesh), mesh


# ---------------------------------------------------------------------------
# Cross-shard merge vs lax.top_k — pure array math, runs on any device count
# ---------------------------------------------------------------------------


def _forward_shards(omega: np.ndarray, n_shards: int, m: int):
    """What each shard emits (exactly ``_sharded_screen``'s per-shard logic,
    replayed in numpy): local top-M via lax.top_k + the masked-argmax
    witness, tagged with global indices."""
    t = len(omega) // n_shards
    scores, idxs = [], []
    for s in range(n_shards):
        blk = omega[s * t : (s + 1) * t]
        s_loc, p_loc = jax.lax.top_k(jnp.asarray(blk), m)
        s_loc, p_loc = np.asarray(s_loc), np.asarray(p_loc)
        mask = np.zeros(t, bool)
        mask[p_loc] = True
        out = np.where(mask, np.float32(NEG_INF), blk)
        scores.append(np.concatenate([s_loc, [out.max()]]))
        idxs.append(np.concatenate([p_loc, [out.argmax()]]) + s * t)
    return (
        np.concatenate(scores).astype(np.float32),
        np.concatenate(idxs).astype(np.int32),
    )


def _oracle(omega: np.ndarray, m: int):
    """The unsharded selection: lax.top_k shortlist + masked-argmax witness."""
    _, cand = jax.lax.top_k(jnp.asarray(omega), m)
    cand = np.asarray(cand)
    mask = np.zeros(len(omega), bool)
    mask[cand] = True
    out = np.where(mask, np.float32(NEG_INF), omega)
    return cand, np.float32(out.max()), np.int32(out.argmax())


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("n_shards,m", [(2, 4), (4, 8), (8, 16)])
def test_merge_preserves_topk_tie_ordering(seed, n_shards, m):
    """Regression: the merged shortlist must list hosts in exactly
    ``lax.top_k``'s order — value descending, ties by ascending index —
    and yield the identical (u, j_u) witness.  Scores are drawn from a
     4-value set so ties dominate (the regime where a sloppy merge breaks)."""
    rng = np.random.default_rng(seed)
    t = max(m + 1, 12)
    omega = rng.choice(
        np.asarray([NEG_INF, 0.25, 0.5, 1.0], np.float32), n_shards * t
    )
    scores, idxs = _forward_shards(omega, n_shards, m)
    cand, u, j_u = merge_shortlists(jnp.asarray(scores), jnp.asarray(idxs), m)
    ref_cand, ref_u, ref_ju = _oracle(omega, m)
    np.testing.assert_array_equal(np.asarray(cand), ref_cand)
    assert np.float32(u) == ref_u
    # j_u is decision-relevant only when u is a real score (see
    # _decision_core's admissibility predicate): at u == NEG_INF the
    # unsharded masked argmax may surface an in-shortlist index while the
    # merge returns the best true outsider — both inert.
    if ref_u > NEG_INF / 2:
        assert int(j_u) == ref_ju


def test_merge_drops_duplicate_witness():
    """A shard whose hosts ALL sit in its local top-M re-emits one of them
    (at NEG_INF) as its witness; the dedup pass must drop the duplicate so
    the merged shortlist stays duplicate-free like lax.top_k's."""
    omega = np.asarray([NEG_INF] * 4 + [1.0, 0.5, NEG_INF, NEG_INF], np.float32)
    scores, idxs = _forward_shards(omega, n_shards=2, m=4)
    assert len(np.unique(idxs)) < len(idxs)  # the degenerate shard duplicated
    cand, _, _ = merge_shortlists(jnp.asarray(scores), jnp.asarray(idxs), 4)
    cand = np.asarray(cand)
    assert len(np.unique(cand)) == len(cand)
    np.testing.assert_array_equal(cand, _oracle(omega, 4)[0])


# ---------------------------------------------------------------------------
# Padding invariance — single device is enough
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("preemptible", [False, True])
def test_padded_state_decisions_unchanged(preemptible):
    """All-zero padding rows are invalid everywhere, so decisions on a padded
    state are bit-identical to the unpadded ones (the property that makes
    N-not-divisible-by-S fleets shardable at all)."""
    rng = np.random.default_rng(3)
    hosts = _random_fleet(rng, 21)
    state, _ = build_soa_state(hosts, NOW, PeriodCost(), k_slots=8)
    padded = pad_fleet_state(state, 40)
    req = jnp.asarray(SIZES[1].vec, jnp.float32)
    for m in (0, 4, 16):
        pol = SchedulerPolicy(shortlist=m)
        a = schedule_decision(state, req, preemptible, -1, policy=pol)
        b = schedule_decision(padded, req, preemptible, -1, policy=pol)
        assert tuple(map(int, a)) == tuple(map(int, b))


# ---------------------------------------------------------------------------
# Sharded vs unsharded decisions — shard_map across forced host devices
# ---------------------------------------------------------------------------


@multi_device
@pytest.mark.parametrize("n_hosts", [37, 64, 101])  # 37/101 ∤ any shard count
@pytest.mark.parametrize("m", [8, 16])
@pytest.mark.parametrize("fused", [False, True])
def test_sharded_step_parity(n_hosts, m, fused):
    """schedule_step: all six outputs (decision + kill mask + health
    signals) bit-equal between the sharded and unsharded screens, across
    fleets whose size does and does not divide the mesh.  ``fused=True``
    runs the per-shard screen through the split Pallas kernel (interpret
    mode on CPU) — the kernel+mesh combination that used to be mutually
    exclusive."""
    rng = np.random.default_rng(n_hosts)
    padded, sharded, mesh = _sharded_pair(_random_fleet(rng, n_hosts), m)
    for step, pre in ((0, False), (1, True), (2, False)):
        req = np.asarray(SIZES[step % 3].vec, np.float32)
        _, ref = schedule_step(
            padded, req, pre, np.int32(-1), NOW + 60.0 * step, 1.0,
            policy=SchedulerPolicy(shortlist=m), donate=False,
        )
        _, got = schedule_step(
            sharded, req, pre, np.int32(-1), NOW + 60.0 * step, 1.0,
            policy=SchedulerPolicy(
                shortlist=m, mesh=mesh, fused_screen=fused or None
            ),
            donate=False,
        )
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@multi_device
def test_sharded_many_parity_and_state():
    """schedule_many: the scan carries the sharded state through decide +
    apply; outputs AND the final state arrays must match the unsharded run
    bitwise (the transitions run on sharded buffers via GSPMD)."""
    rng = np.random.default_rng(17)
    padded, sharded, mesh = _sharded_pair(_random_fleet(rng, 50), 8)
    b = 12
    res = np.stack(
        [np.asarray(SIZES[i % 3].vec, np.float32) for i in range(b)]
    )
    pre = np.asarray([i % 2 == 0 for i in range(b)])
    dom = np.full((b,), -1, np.int32)
    now = NOW + 60.0 * np.arange(b, dtype=np.float32)
    price = np.ones((b,), np.float32)
    ref_state, ref = schedule_many(
        padded, res, pre, dom, now, price,
        policy=SchedulerPolicy(shortlist=8), donate=False,
    )
    got_state, got = schedule_many(
        sharded, res, pre, dom, now, price,
        policy=SchedulerPolicy(shortlist=8, mesh=mesh), donate=False,
    )
    for a, c in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    for a, c in zip(
        jax.tree_util.tree_leaves(ref_state),
        jax.tree_util.tree_leaves(got_state),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


@multi_device
def test_sharded_fallback_parity():
    """The loose-bound fixture from test_shortlist_parity, sharded: host A's
    cost lower bound undershoots (cheap slots conflict across dims), a
    1-candidate shortlist picks A optimistically, and the admissibility
    check must take the lax.cond full-enumeration branch — on SHARDED
    arrays — landing on the true winner B."""
    mesh = fleet_mesh()
    from repro.core.jax_scheduler import SoAHostState

    free_f = np.zeros((2, 2), np.float32)
    free_n = np.full((2, 2), 4.0, np.float32)
    inst_res = np.array(
        [[[4, 0], [0, 4], [4, 4]], [[4, 4], [0, 0], [0, 0]]], np.float32
    )
    inst_cost = np.array([[10, 10, 50], [15, 0, 0]], np.float32)
    inst_valid = np.array([[1, 1, 1], [1, 0, 0]], bool)
    state = SoAHostState(
        free_f=jnp.asarray(free_f),
        free_n=jnp.asarray(free_n),
        schedulable=jnp.ones((2,), bool),
        domain=jnp.zeros((2,), jnp.int32),
        slow=jnp.ones((2,), jnp.float32),
        inst_res=jnp.asarray(inst_res),
        inst_cost=jnp.asarray(inst_cost),
        inst_valid=jnp.asarray(inst_valid),
    )
    padded = pad_fleet_state(state, padded_hosts(2, mesh.size, m_keep=2))
    sharded = shard_fleet_state(padded, mesh)
    req = jnp.asarray([4.0, 4.0], jnp.float32)
    ref = schedule_decision(
        padded, req, False, -1, policy=SchedulerPolicy(shortlist=1)
    )
    for fused in (None, True):
        got = schedule_decision(
            sharded, req, False, -1,
            policy=SchedulerPolicy(shortlist=1, mesh=mesh, fused_screen=fused),
        )
        assert tuple(map(int, got)) == tuple(map(int, ref)), f"fused={fused}"
    assert int(ref[0]) == 1 and bool(ref[2])  # B's single 15-cost slot wins


@multi_device
def test_sharded_fleet_end_to_end():
    """SoAFleet(mesh=...): padding + placement at build, sharded decisions,
    donation, and python bookkeeping — outcome-for-outcome equal to the
    unsharded fleet over a mixed schedule/depart/fail/batch run.  Also
    exercises non-integer slot costs (RevenueCost) where the admissibility
    tolerance is live."""
    rng = np.random.default_rng(23)
    hosts = _random_fleet(rng, 43)
    plain = SoAFleet(
        hosts, cost_fn=RevenueCost(), k_slots=8,
        policy=SchedulerPolicy.for_cost(RevenueCost(), shortlist=8),
    )
    sharded = SoAFleet(
        _random_fleet(np.random.default_rng(23), 43),
        cost_fn=RevenueCost(), k_slots=8,
        policy=SchedulerPolicy.for_cost(
            RevenueCost(), shortlist=8, mesh=fleet_mesh()
        ),
    )
    assert sharded.state.n_hosts % sharded.mesh.size == 0

    def drive(fleet):
        log = []
        out = fleet.schedule_batch(
            [
                (
                    Request(
                        id=f"r{i}", resources=SIZES[i % 3],
                        preemptible=bool(i % 2),
                    ),
                    NOW + 60.0 * i,
                    1.0,
                )
                for i in range(10)
            ]
        )
        log += [(o.host, o.ok, tuple(v.id for v in o.victims)) for o in out]
        placed = next(o for o in out if o.ok)
        fleet.depart(placed.instance.id)
        fleet.fail_host("h3")
        o = fleet.schedule_request(
            Request(id="rx", resources=SIZES[2], preemptible=False),
            NOW + 3600.0,
        )
        log.append((o.host, o.ok, tuple(v.id for v in o.victims)))
        log.append(round(fleet.utilization(), 6))
        return log

    assert drive(plain) == drive(sharded)


@multi_device
@pytest.mark.parametrize("fused", [False, True])
def test_sharded_mixed_cost_parity(fused):
    """Heterogeneous billing on the sharded path: a fleet mixing all four
    cost kinds (per-instance ``cost_kind``) must make bit-identical
    decisions sharded vs unsharded — the kind-table select runs upstream of
    the screen, so sharding (and the per-shard fused kernel) must be
    transparent to it."""
    kinds = ("period", "count", "revenue", "recompute")
    rng = np.random.default_rng(77)
    hosts = _random_fleet(rng, 41)
    for h in hosts:
        for inst in h.preemptible_instances():
            inst.cost_kind = kinds[int(rng.integers(4))]
            inst.last_checkpoint = inst.start_time + 120.0
    policy = SchedulerPolicy.for_cost(
        MixedCost(default="period", kinds=kinds), shortlist=8
    )
    mesh = fleet_mesh()
    state, _ = build_fleet_state(hosts, k_slots=8)
    padded = pad_fleet_state(state, padded_hosts(41, mesh.size, m_keep=9))
    sharded = shard_fleet_state(padded, mesh)
    for step, pre in ((0, False), (1, True), (2, False)):
        req = np.asarray(SIZES[step % 3].vec, np.float32)
        kind = np.int32(step % 4)
        _, ref = schedule_step(
            padded, req, pre, np.int32(-1), NOW + 60.0 * step, 1.0,
            policy=policy, req_cost_kind=kind, donate=False,
        )
        _, got = schedule_step(
            sharded, req, pre, np.int32(-1), NOW + 60.0 * step, 1.0,
            policy=dataclasses.replace(
                policy, mesh=mesh, fused_screen=fused or None
            ),
            req_cost_kind=kind, donate=False,
        )
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the mixed column actually varies (otherwise this test is vacuous)
    col = np.asarray(padded.inst_cost_kind)[np.asarray(padded.inst_valid)]
    assert len(np.unique(col)) == 4


@multi_device
@pytest.mark.parametrize("fused", [False, True])
def test_sharded_churn_parity(fused):
    """Failure-domain plane, sharded: zone accumulators are replicated
    across the mesh while ``host_zone`` shards host-major, and churn-aware
    decisions (weigher term + hot-zone threshold) must stay bit-identical
    to the unsharded screen — including the per-shard churn-normalization
    folds crossing the pmin/pmax merge."""
    rng = np.random.default_rng(29)
    hosts = _random_fleet(rng, 39)  # 39 does not divide the mesh
    for i, h in enumerate(hosts):
        h.zone = f"z{i % 3}"
    mesh = fleet_mesh()
    # seeded accumulator history: z0 cold, z1 warm, z2 hot (ẑ = 0.5)
    state, _ = build_fleet_state(
        hosts, k_slots=8,
        zone_term=np.asarray([0.0, 8.0, 32.0], np.float32),
        zone_up=np.asarray([64.0, 64.0, 64.0], np.float32),
    )
    padded = pad_fleet_state(state, padded_hosts(39, mesh.size, m_keep=9))
    sharded = shard_fleet_state(padded, mesh)
    np.testing.assert_array_equal(  # zone plane survives pad + shard
        np.asarray(sharded.zone_term), np.asarray(state.zone_term)
    )
    policy = SchedulerPolicy(
        shortlist=8, churn_multiplier=2.0, churn_threshold=0.25
    )
    for step, pre in ((0, False), (1, True), (2, False)):
        req = np.asarray(SIZES[step % 3].vec, np.float32)
        _, ref = schedule_step(
            padded, req, pre, np.int32(-1), NOW + 60.0 * step, 1.0,
            policy=policy, donate=False,
        )
        _, got = schedule_step(
            sharded, req, pre, np.int32(-1), NOW + 60.0 * step, 1.0,
            policy=dataclasses.replace(
                policy, mesh=mesh, fused_screen=fused or None
            ),
            donate=False,
        )
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"step {step}"
            )


@multi_device
def test_sharded_simulator_smoke():
    """SoASimulator(mesh=...) runs the whole event loop on the sharded state
    and produces identical metrics to the unsharded simulator (same seed ⇒
    same rng stream ⇒ decisions must agree for the runs to align)."""
    from repro.core import SoASimulator, WorkloadSpec, make_uniform_fleet

    node = VM_SPEC.make(vcpus=8, ram_mb=16000, disk_gb=10_000)
    workload = WorkloadSpec(
        arrival_rate_per_s=0.05,
        preemptible_fraction=0.6,
        flavors=(("small", SIZES[0]), ("medium", SIZES[1])),
        flavor_probs=(0.5, 0.5),
    )
    runs = []
    for mesh in (None, fleet_mesh()):
        sim = SoASimulator(
            make_uniform_fleet(44, node), workload, seed=5,
            cost_fn=PeriodCost(), k_slots=8,
            policy=SchedulerPolicy(shortlist=8, mesh=mesh),
        )
        summary = sim.run(1800.0).summary()
        # sched_latency_* are wall-clock timings — everything else is a pure
        # function of the decisions and must match exactly.
        runs.append(
            {k: v for k, v in summary.items() if "latency" not in k}
        )
    assert runs[0] == runs[1]


@multi_device
def test_chip_smoke_sharded_phase_tiny():
    """chip_smoke.py's ``--chips 4`` phase at a small fleet: the sharded
    fleet's decisions and arrays equal the unsharded fleet's bitwise, and
    the phase's own check finds the mesh screen's all-gather."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    out = smoke.sharded_phase(
        n_hosts=512, n_requests=48, seed=1, mesh=fleet_mesh(4)
    )
    assert out["shards"] == 4 and out["placed"] > 0
