"""Python-side mirror of the persistent device-resident fleet state.

``SoAFleet`` owns a ``SoAFleetState`` (the arrays the jit'd scheduler reads
and writes incrementally) plus the minimal python bookkeeping the arrays
cannot carry: instance identities, the slot ↔ instance-id map, and the
records needed to materialize ``Host`` objects again.  Every mutation goes
through the pure jnp transitions in ``jax_scheduler`` — the arrays are never
rebuilt from python objects on the hot path (that rebuild, ``build_fleet_state``,
remains the correctness oracle; see tests/test_soa_incremental.py).

Sync discipline: per-event work touches only O(K) scalars (the decision
outputs); full python ``Host`` objects are materialized only on demand
(``sync_hosts`` — e.g. at simulator sample points or for verification).
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import obs
from .admission import AdmissionFrontEnd, DrainResult, PAD_RES
from .cost import CostFunction
from .jax_scheduler import (
    DEFAULT_SHORTLIST,
    SoAFleetState,
    apply_checkpoint,
    apply_departure,
    apply_host_failure,
    apply_termination,
    build_fleet_state,
    jax_cost_params,  # noqa: F401  (back-compat re-export)
    relocate_many,
    schedule_many,
    schedule_step,
    set_schedulable,
    set_slow_factor,
)
from .policy import (
    COST_KIND_IDS,
    SchedulerPolicy,
    ensure_policy,
)
from .screen_math import NEG_INF, churn_stats, floor_mod
from .types import Host, Instance, Request, Resources

#: Padding sentinel for batched scheduling: a request no host can fit
#: (shared with the admission drain's untaken rows).
_PAD_RES = PAD_RES


@dataclasses.dataclass
class AdaptiveShortlist:
    """Host-side shortlist-size controller over the jit'd decision paths.

    The stage-2 shortlist size M is a compile-time constant of the decision
    executables, so the controller adapts *between* calls on the python side
    using the health signals every step/batch already returns
    (``fell_back``, ``margin`` — see ``jax_scheduler.schedule_many``):

      * grow (×2 up to ``m_max``) after ``grow_after`` consecutive flushes
        that contained an admissibility fallback — the shortlist was too
        small to certify its winner and the decision paid the full O(N·2^K)
        enumeration;
      * shrink (÷2 down to ``m_min``) after ``shrink_after`` consecutive
        fallback-free flushes whose smallest admissibility margin stayed
        above ``wide_margin`` (weigher-score units; the default multipliers
        put one weigher term in [0, 1], so 0.25 is "a quarter of a term of
        headroom beyond every non-shortlisted bound").

    M stays a power of two in [m_min, m_max] (``SchedulerPolicy.
    adaptive_bounds``, validated at construction), so the jit cache holds at
    most log2(m_max/m_min)+1 decision executables per request shape.

    Defaults (grow_after=2, shrink_after=8, wide_margin=0.25) come from the
    ``screen_adaptive_*`` workload study in benchmarks/bench_screen.py
    (rows in benchmarks/results/BENCH_screen.json), which sweeps the
    thresholds over two extreme synthetic fleets at N=4096:

      * *fallback-heavy* (every host's stage-1 bound undershoots, so small
        M can never certify a winner): grow_after ≤ 2 escapes the fallback
        storm within two flushes — 29/104 decisions fell back before the
        controller reached an M that certifies, then zero after — while
        grow_after=4 never grew within a 100-decision horizon and kept
        paying the full O(N·2^K) enumeration;
      * *calm sparse-feasibility* (the whole viable pool fits in the
        shortlist, margins effectively infinite): shrink_after=8 steps M
        down steadily (64→32 over ~100 decisions) without thrash, while
        shrink_after=4 reaches the floor twice as fast but — like
        grow_after=1 — pays a fresh XLA compile per M move (~35 ms/flush
        amortized on the study box vs ~1 ms at the defaults), which is the
        real cost of a twitchy controller.

    CPU caveat: XLA CPU rewrites ``lax.top_k`` to its fast TopK custom-call
    only for k ≤ 64, so on CPU backends growing past M=64 adds a full fleet
    sort (~22 ms at N=65536) on top of the larger stage 2 — the growth path
    really pays off on TPU (fused screen) or when fallbacks are burning far
    more than the sort.
    """

    m: int = DEFAULT_SHORTLIST
    m_min: int = 16
    m_max: int = 256
    grow_after: int = 2
    shrink_after: int = 8
    wide_margin: float = 0.25
    #: counters (exposed via ``SoAFleet.shortlist_stats``)
    grows: int = 0
    shrinks: int = 0
    _fallback_streak: int = dataclasses.field(default=0, repr=False)
    _calm_streak: int = dataclasses.field(default=0, repr=False)

    def update(self, n_fallbacks: int, min_margin: float) -> None:
        """Fold one flush's signals; possibly step M."""
        if n_fallbacks > 0:
            self._fallback_streak += 1
            self._calm_streak = 0
            if self._fallback_streak >= self.grow_after and self.m < self.m_max:
                self.m = min(self.m * 2, self.m_max)
                self.grows += 1
                self._fallback_streak = 0
        else:
            self._fallback_streak = 0
            self._calm_streak += 1
            if (
                self._calm_streak >= self.shrink_after
                and min_margin > self.wide_margin
                and self.m > self.m_min
            ):
                self.m = max(self.m // 2, self.m_min)
                self.shrinks += 1
                self._calm_streak = 0


@dataclasses.dataclass(frozen=True)
class SoAOutcome:
    """One decision of the fast path, translated back to python identities."""

    request: Request
    host: Optional[str]                  # None = failed
    instance: Optional[Instance]         # the placed record
    victims: Tuple[Instance, ...] = ()   # evacuated preemptible instances

    @property
    def ok(self) -> bool:
        return self.host is not None


#: one jit'd program behind every host-side churn read (see churn_snapshot)
_churn_stats_jit = jax.jit(churn_stats)


@functools.partial(jax.jit, static_argnames=("budget",))
def _relocation_victims(state, zone, now, default_period, budget: int):
    """Checkpoint-aware victim selection on device: rank ``zone``'s live
    preemptible slots by the loss a reclaim would cause RIGHT NOW —
    recompute work since the last durable checkpoint (the RecomputeCost
    convention: lost seconds × chips, dim 0) plus the remaining prepaid
    billing period (per-slot ``inst_period``; -1 sentinel = the policy's
    shared ``default_period``) — and return the at-most-``budget``
    highest-loss slots, ties by lowest flat index (``lax.top_k``).

    Returns ``(host (B,), slot (B,), valid (B,))``; rows with
    ``valid=False`` gathered a dead/foreign slot (fewer live slots in the
    zone than the budget) and must be skipped.
    """
    live = state.inst_valid & (state.host_zone[:, None] == zone)
    recompute = jnp.maximum(0.0, now - state.inst_ckpt) * jnp.maximum(
        1.0, state.inst_res[..., 0]
    )
    period = jnp.where(
        state.inst_period > 0, state.inst_period, default_period
    )
    remaining = period - floor_mod(now - state.inst_start, period)
    loss = jnp.where(live, recompute + remaining, NEG_INF)
    k = state.inst_valid.shape[1]
    top, idx = jax.lax.top_k(loss.reshape(-1), budget)
    return idx // k, idx % k, top > NEG_INF / 2


@dataclasses.dataclass
class _ZoneReloc:
    """Per-zone hysteresis + retry record of the relocation plane.

    ``armed`` flips on when ẑ crosses ``policy.relocate_threshold`` (and
    the cooldown has expired) and off when ẑ falls below the lower
    ``relocate_exit_threshold`` — the two-threshold hysteresis that keeps
    an oscillating zone from thrashing.  ``retry_at`` is the exponential
    backoff gate failed re-placements push forward."""

    armed: bool = False
    cooldown_until: float = float("-inf")
    fail_streak: int = 0
    retry_at: float = float("-inf")


@dataclasses.dataclass
class RelocationStats:
    """Host-side counters of the relocation plane (one per fleet).

    Conservation: every ``attempted`` victim ends in exactly one of
    ``relocated`` (moved; victim departed voluntarily after its replacement
    placed), ``failed`` (re-placement rejected; victim untouched),
    ``lost_victims`` (reclaimed mid-flight; the replacement stands as the
    checkpoint restore), ``stale`` (victim departed on its own mid-flight;
    the surplus replacement departed immediately), or ``pending`` (still
    in the admission queue)."""

    passes: int = 0
    arms: int = 0
    disarms: int = 0
    attempted: int = 0
    relocated: int = 0
    failed: int = 0
    lost_victims: int = 0
    stale: int = 0
    pending: int = 0

    def summary(self) -> Dict[str, float]:
        return {
            "relocation_passes": float(self.passes),
            "relocation_arms": float(self.arms),
            "relocation_disarms": float(self.disarms),
            "relocation_attempted": float(self.attempted),
            "relocations": float(self.relocated),
            "relocation_failed": float(self.failed),
            "relocation_lost": float(self.lost_victims),
            "relocation_stale": float(self.stale),
            "relocation_pending": float(self.pending),
        }


class SoAFleet:
    """Incremental fleet view: device arrays + id bookkeeping.

    All decision knobs live on ONE ``SchedulerPolicy`` (``core.policy``)
    threaded straight through to ``jax_scheduler`` as the single static jit
    argument.  The execution knobs (``shortlist``, ``fused_screen``,
    ``mesh``, ``use_pallas``, ``adaptive_shortlist``) select *which path
    computes the answer*, never the answer itself; the weigher multipliers
    and the cost-kind table define the provider policy proper.  A mixed
    cost table (``policy.cost_kinds`` non-empty / ``cost_fn=MixedCost``)
    bills each instance by its own ``cost_kind`` via the state's
    ``inst_cost_kind`` column.

    ``policy.mesh`` pads the state (``fleet_sharding.padded_hosts``) and
    places it across the mesh at build; stage 1 then runs per shard under
    ``shard_map`` with a bit-exact cross-shard merge.

    With ``policy.queue_capacity > 0`` the fleet additionally carries a
    streaming admission front end (``core.admission``): arrivals go through
    ``submit`` (admit-or-queue) and decisions happen at ``drain`` time in
    priority order with backfill retries; the direct entry points
    (``schedule_request``/``schedule_batch``) stay available and bypass the
    queue.
    """

    def __init__(
        self,
        hosts: Sequence[Host],
        cost_fn: Optional[CostFunction] = None,
        k_slots: int = 8,
        policy: Optional[SchedulerPolicy] = None,
    ):
        self.policy = ensure_policy(policy, "SoAFleet", cost_fn=cost_fn)
        self.cost_fn = cost_fn or self.policy.make_cost_fn()
        self.k_slots = k_slots
        #: optional host-side controller steering M between flushes
        #: (bounds + starting M from the policy).
        self.adaptive: Optional[AdaptiveShortlist] = (
            AdaptiveShortlist(
                m=(
                    DEFAULT_SHORTLIST
                    if self.policy.shortlist is None
                    else self.policy.shortlist
                ),
                m_min=self.policy.adaptive_bounds[0],
                m_max=self.policy.adaptive_bounds[1],
            )
            if self.policy.adaptive_shortlist
            else None
        )
        #: admissibility-fallback totals (every flush, adaptive or not)
        self.decisions = 0
        self.fallbacks = 0

        self.names: List[str] = [h.name for h in hosts]
        self.index: Dict[str, int] = {n: i for i, n in enumerate(self.names)}
        self.capacity: List[Resources] = [h.capacity for h in hosts]
        self.spec = hosts[0].capacity.spec if hosts else None
        self.domains: List[str] = [h.domain for h in hosts]
        self.domain_ids: Dict[str, int] = {}
        for h in hosts:
            self.domain_ids.setdefault(h.domain, len(self.domain_ids))
        #: failure-domain (zone) plane: zone label per host + insertion-order
        #: zone ids, mirroring the state's ``host_zone`` column and the
        #: per-zone churn accumulators (``zone_term``/``zone_up``).
        self.zones: List[str] = [h.zone for h in hosts]
        self.zone_ids: Dict[str, int] = {}
        for h in hosts:
            self.zone_ids.setdefault(h.zone, len(self.zone_ids))

        # Mixed-payment fleets must declare every kind they bill: an
        # instance carrying a kind outside the policy table is a
        # configuration error, caught here instead of mid-decision.
        table = self.policy.kind_table
        for h in hosts:
            for inst in h.instances.values():
                if inst.cost_kind is not None and inst.cost_kind not in table:
                    raise ValueError(
                        f"instance {inst.id} bills by {inst.cost_kind!r}, "
                        f"not in the policy's cost-kind table {table}"
                    )

        self.state, slot_rows = build_fleet_state(
            hosts, k_slots=k_slots, domain_ids=self.domain_ids,
            zone_ids=self.zone_ids,
        )
        if self.policy.mesh is not None:
            # Pad to a shard-divisible host count that leaves every shard
            # room for the largest shortlist this fleet can run (the
            # adaptive ceiling when the controller is on), then place the
            # arrays host-major across the mesh.  Padding rows are invalid
            # everywhere, so decisions are unchanged (tests/test_sharded_parity).
            from .fleet_sharding import (
                pad_fleet_state, padded_hosts_for, shard_fleet_state,
            )

            self.state = shard_fleet_state(
                pad_fleet_state(
                    self.state, padded_hosts_for(len(hosts), self.policy)
                ),
                self.policy.mesh,
            )
        #: slot → live preemptible instance id (None = free slot)
        self.slot_ids: List[List[Optional[str]]] = [
            [inst.id if inst is not None else None for inst in row]
            for row in slot_rows
        ]
        #: all live instances, including normal ones
        self.instances: Dict[str, Instance] = {}
        #: id → (host_idx, slot) — slot None for normal instances
        self.locator: Dict[str, Tuple[int, Optional[int]]] = {}
        for i, h in enumerate(hosts):
            for inst in h.instances.values():
                self.instances[inst.id] = inst
                slot = (
                    self.slot_ids[i].index(inst.id) if inst.preemptible else None
                )
                self.locator[inst.id] = (i, slot)

        self.preempted: List[Instance] = []
        self._ids = itertools.count()
        #: relocation plane (armed per zone by policy.relocate_threshold)
        self.relocation = RelocationStats()
        self._reloc_zone: Dict[str, _ZoneReloc] = {}
        #: victims whose re-placement is waiting in the admission queue
        self._reloc_inflight: Set[str] = set()
        #: relocated old id → replacement id; the simulator follows this
        #: chain when a departure event names a relocated instance
        self.relocated_ids: Dict[str, str] = {}
        cap = np.stack([c.vec for c in self.capacity]) if hosts else np.zeros((0, 1))
        self._cap0_total = float(cap[:, 0].sum())

        #: streaming admission front end (None = admission plane off)
        self.admission: Optional[AdmissionFrontEnd] = (
            AdmissionFrontEnd(self) if self.policy.queue_capacity else None
        )

    # -- back-compat views of the policy fields ------------------------------
    @property
    def cost_kind(self) -> str:
        return self.policy.cost_kind

    @property
    def period(self) -> float:
        return self.policy.period

    @property
    def use_pallas(self) -> bool:
        return self.policy.use_pallas

    @property
    def weigher_multipliers(self) -> Tuple[float, float, float, float]:
        return self.policy.weigher_multipliers

    @property
    def shortlist(self) -> Optional[int]:
        return self.policy.shortlist

    @property
    def fused_screen(self) -> Optional[bool]:
        return self.policy.fused_screen

    @property
    def mesh(self):
        return self.policy.mesh

    # -- derived metrics (device reductions; no python Host objects) ---------
    @property
    def n_hosts(self) -> int:
        return len(self.names)

    def utilization(self) -> float:
        if not self._cap0_total:
            return 0.0
        free0 = float(self.state.free_f[:, 0].sum())
        return (self._cap0_total - free0) / self._cap0_total

    def utilization_normal(self) -> float:
        if not self._cap0_total:
            return 0.0
        free0 = float(self.state.free_n[:, 0].sum())
        return (self._cap0_total - free0) / self._cap0_total

    # -- scheduling ----------------------------------------------------------
    def _req_arrays(self, req: Request):
        dom = -1 if req.domain is None else self.domain_ids.get(req.domain, -1)
        if req.cost_kind is None:
            kind = -1
        else:
            if req.cost_kind not in self.policy.kind_table:
                raise ValueError(
                    f"request {req.id} bills by {req.cost_kind!r}, not in "
                    f"the policy's cost-kind table {self.policy.kind_table}"
                )
            kind = COST_KIND_IDS[req.cost_kind]
        if req.exclude_zone is None:
            excl = -1
        else:
            # Fail closed: a typo'd zone name silently matching nothing
            # would void the never-place-back guarantee.
            if req.exclude_zone not in self.zone_ids:
                raise ValueError(
                    f"request {req.id} excludes unknown zone "
                    f"{req.exclude_zone!r}; fleet zones: "
                    f"{sorted(self.zone_ids)}"
                )
            excl = self.zone_ids[req.exclude_zone]
        return (
            req.resources.vec32,
            bool(req.preemptible),
            np.int32(dom),
            np.int32(kind),
            np.float32(-1.0 if req.period is None else req.period),
            np.int32(excl),
        )

    @property
    def effective_shortlist(self) -> Optional[int]:
        """The M the next flush will use (controller-steered when adaptive)."""
        return self.adaptive.m if self.adaptive is not None else self.shortlist

    def _flush_policy(self) -> SchedulerPolicy:
        """The policy the next flush dispatches with: the fleet policy, with
        M swapped in when the adaptive controller moved it.  Equal policies
        hash alike, so this re-hits the jit cache (≤ log2(m_max/m_min)+1
        distinct executables per request shape)."""
        m = self.effective_shortlist
        if m == self.policy.shortlist:
            return self.policy
        return dataclasses.replace(self.policy, shortlist=m)

    @property
    def shortlist_stats(self) -> Dict[str, int]:
        """Shortlist-health counters: decisions seen, admissibility
        fallbacks paid, and the adaptive controller's moves (0s when the
        controller is off).  ``shortlist`` is the M decisions actually run
        with — ``shortlist=None`` resolves to the same auto value the
        decision core uses (M=64 at fleet scale, 0 = full enumeration on
        small fleets)."""
        a = self.adaptive
        m = self.effective_shortlist
        if m is None:  # mirror _decision_core's auto rule (padded state size)
            m = (
                DEFAULT_SHORTLIST
                if self.state.n_hosts > 4 * DEFAULT_SHORTLIST
                else 0
            )
        return {
            "decisions": self.decisions,
            "fallbacks": self.fallbacks,
            "shortlist": m,
            "grows": a.grows if a else 0,
            "shrinks": a.shrinks if a else 0,
        }

    def _observe(self, n_fallbacks: int, min_margin: float, n_decisions: int):
        self.decisions += n_decisions
        self.fallbacks += n_fallbacks
        if self.adaptive is not None:
            self.adaptive.update(n_fallbacks, min_margin)

    def schedule_request(
        self, req: Request, now: float, price: float = 1.0
    ) -> SoAOutcome:
        """One decide-and-apply step on the persistent state."""
        res, pre, dom, kind, period, excl = self._req_arrays(req)
        self.state, (host_idx, slot, ok, kill, fell_back, margin) = schedule_step(
            self.state, res, pre, dom, now, price,
            policy=self._flush_policy(), req_cost_kind=kind, req_period=period,
            req_exclude_zone=excl,
        )
        self._observe(int(fell_back), float(margin), 1)
        return self._absorb(
            req, now, price, int(host_idx), int(slot), bool(ok), np.asarray(kill)
        )

    def schedule_batch(
        self, items: Sequence[Tuple[Request, float, float]]
    ) -> List[SoAOutcome]:
        """Run ``(request, now, price)`` triples through one ``lax.scan``.

        The batch is padded to the next power of two with unsatisfiable
        sentinel requests so jit recompiles only O(log B) distinct shapes.
        """
        if not items:
            return []
        if len(items) == 1:  # fused single step — no scan compile for B=1
            req, t, p = items[0]
            return [self.schedule_request(req, t, price=p)]
        b = len(items)
        # floor of 4 keeps the number of distinct compiled scan lengths small
        padded = max(4, 1 << (b - 1).bit_length())
        d = len(self.spec.dims)
        res = np.full((padded, d), _PAD_RES, np.float32)
        pre = np.zeros((padded,), bool)
        dom = np.full((padded,), -1, np.int32)
        now = np.full((padded,), items[-1][1], np.float32)
        price = np.ones((padded,), np.float32)
        kind = np.full((padded,), -1, np.int32)
        period = np.full((padded,), -1.0, np.float32)
        excl = np.full((padded,), -1, np.int32)
        for i, (req, t, p) in enumerate(items):
            (res[i], pre[i], dom[i], kind[i], period[i],
             excl[i]) = self._req_arrays(req)
            now[i] = t
            price[i] = p
        self.state, (host_idx, slot, ok, kill, fell_back, margin) = schedule_many(
            self.state, res, pre, dom, now, price,
            policy=self._flush_policy(), req_cost_kind=kind, req_period=period,
            req_exclude_zone=excl,
        )
        host_idx, slot = np.asarray(host_idx), np.asarray(slot)
        ok, kill = np.asarray(ok), np.asarray(kill)
        # Health signals from the REAL rows only (padding sentinels can
        # neither fall back nor tighten the margin, but stay out anyway).
        fb = np.asarray(fell_back)[:b]
        mg = np.asarray(margin)[:b]
        self._observe(int(fb.sum()), float(mg.min()), b)
        return [
            self._absorb(
                req, t, p, int(host_idx[i]), int(slot[i]), bool(ok[i]), kill[i]
            )
            for i, (req, t, p) in enumerate(items)
        ]

    def _absorb(
        self,
        req: Request,
        now: float,
        price: float,
        host_idx: int,
        slot: int,
        ok: bool,
        kill_row: np.ndarray,
    ) -> SoAOutcome:
        """Fold one decision's outputs back into the python bookkeeping."""
        if not ok:
            return SoAOutcome(request=req, host=None, instance=None)
        name = self.names[host_idx]
        victims: List[Instance] = []
        if not req.preemptible:
            for k in np.flatnonzero(kill_row):
                vid = self.slot_ids[host_idx][k]
                assert vid is not None, "terminated an empty slot"
                victim = self.instances.pop(vid)
                del self.locator[vid]
                self.slot_ids[host_idx][k] = None
                self.preempted.append(victim)
                victims.append(victim)
        inst = Instance(
            id=f"i{next(self._ids)}-{req.id}",
            resources=req.resources,
            preemptible=req.preemptible,
            host=name,
            start_time=now,
            user=req.user,
            price_rate=price,
            cost_kind=req.cost_kind,
            period=req.period,
        )
        self.instances[inst.id] = inst
        if req.preemptible:
            assert self.slot_ids[host_idx][slot] is None, "slot collision"
            self.slot_ids[host_idx][slot] = inst.id
            self.locator[inst.id] = (host_idx, slot)
            # survives the locator entry (an in-batch preemption may reap
            # this instance before the caller reads the outcome)
            inst.metadata["slot"] = int(slot)
        else:
            self.locator[inst.id] = (host_idx, None)
        return SoAOutcome(
            request=req, host=name, instance=inst, victims=tuple(victims)
        )

    # -- streaming admission (policy.queue_capacity > 0) ---------------------
    def _front(self) -> AdmissionFrontEnd:
        if self.admission is None:
            raise RuntimeError(
                "admission plane is off; build the fleet with "
                "SchedulerPolicy(queue_capacity=...) to use submit/drain"
            )
        return self.admission

    def submit(self, req: Request, now: float, price: float = 1.0) -> None:
        """Admit-or-queue: accept an arrival into the admission plane (the
        decision happens at the next drain, in priority order)."""
        self._front().submit(req, now, price=price)

    def drain(self, now: float, block: bool = True) -> Optional[DrainResult]:
        """Run one admission drain (see ``AdmissionFrontEnd.drain``)."""
        return self._front().drain(now, block=block)

    def drain_all(self, now: float) -> List[DrainResult]:
        """Drain until the queue empties or retries exhaust."""
        return self._front().drain_all(now)

    @property
    def admission_stats(self) -> Dict[str, float]:
        """Counters + latency percentiles of the admission plane."""
        front = self._front()
        front.sync()
        return front.stats.summary()

    # -- lifecycle transitions ----------------------------------------------
    def depart(self, instance_id: str, now: Optional[float] = None) -> bool:
        """Voluntary departure.  Returns False if the instance is already
        gone (preempted / host failure) — departures are idempotent.

        Pass ``now`` to credit the departing slot's accrued uptime to its
        zone's churn denominator (a voluntary exit is evidence the zone is
        *healthy*: uptime without a termination).  Without ``now`` the zone
        accumulators are untouched — the exact pre-churn transition."""
        with obs.span(obs.DEPART):
            inst = self.instances.pop(instance_id, None)
            if inst is None:
                return False
            host_idx, slot = self.locator.pop(instance_id)
            if slot is not None:
                mask = np.zeros((self.k_slots,), bool)
                mask[slot] = True
                self.state = apply_termination(
                    self.state, host_idx, mask, now=now, involuntary=False
                )
                self.slot_ids[host_idx][slot] = None
            else:
                self.state = apply_departure(
                    self.state, host_idx, inst.resources.vec32
                )
            return True

    def preempt_instance(
        self, instance_id: str, now: Optional[float] = None
    ) -> bool:
        """Involuntary out-of-band preemption (storm injection / provider
        reclaim): the instance dies like a scheduler kill — freed on device,
        recorded in ``preempted`` for re-queueing, and (when ``now`` is
        given) charged to its host's zone churn accumulators.  Returns False
        when the instance is already gone (benign — storms and relocations
        race, so reclaims are idempotent); raises for a live NORMAL
        instance, which no provider reclaims out of band (a normal id here
        is a caller bug, not a race)."""
        loc = self.locator.get(instance_id)
        if loc is None:
            return False
        if loc[1] is None:
            raise ValueError(
                f"instance {instance_id} is not preemptible; out-of-band "
                "reclaim only takes preemptible slots (normal instances "
                "leave via depart/fail_host)"
            )
        host_idx, slot = loc
        inst = self.instances.pop(instance_id)
        del self.locator[instance_id]
        mask = np.zeros((self.k_slots,), bool)
        mask[slot] = True
        self.state = apply_termination(
            self.state, host_idx, mask, now=now, involuntary=True
        )
        self.slot_ids[host_idx][slot] = None
        self.preempted.append(inst)
        return True

    def fail_host(self, name: str, now: Optional[float] = None) -> Tuple[int, int]:
        """Hard failure: every instance dies (preemptible ones are recorded
        as preempted for re-queueing).  Returns (n_preempted, n_terminated).

        Pass ``now`` to charge the failure to the host's zone churn
        accumulators (every live slot's termination + accrued uptime)."""
        host_idx = self.index[name]
        n_pre = n_norm = 0
        normal_res = np.zeros((len(self.spec.dims),), np.float32)
        for iid in [
            i for i, (h, _) in self.locator.items() if h == host_idx
        ]:
            inst = self.instances.pop(iid)
            _, slot = self.locator.pop(iid)
            if slot is not None:
                self.slot_ids[host_idx][slot] = None
                self.preempted.append(inst)
                n_pre += 1
            else:
                normal_res += inst.resources.vec32
                n_norm += 1
        self.state = apply_host_failure(
            self.state, host_idx, normal_res, now=now
        )
        return n_pre, n_norm

    # -- failure-domain plane (zone churn readers) ---------------------------
    def churn_snapshot(self) -> Tuple[Dict[str, float], float]:
        """Every churn statistic in ONE fused device reduction + transfer
        (``screen_math.churn_stats``): returns ``(per-zone ẑ by name,
        fleet-wide rate)``.  The single reader behind ``zone_rates``,
        ``fleet_churn_rate``, and the relocation trigger — callers needing
        both halves should call this once instead of both wrappers."""
        out = np.asarray(
            _churn_stats_jit(self.state.zone_term, self.state.zone_up)
        )
        rates = {z: float(out[i]) for z, i in self.zone_ids.items()}
        return rates, float(out[-1])

    def zone_rates(self) -> Dict[str, float]:
        """Observed per-zone churn rates ẑ = T / max(U, eps): involuntary
        terminations over accrued preemptible uptime — the same statistic the
        device decision reads via ``screen_math.churn_of``."""
        return self.churn_snapshot()[0]

    def fleet_churn_rate(self) -> float:
        """Fleet-wide churn rate ΣT / max(ΣU, eps) — the storm signal the
        admission plane's graceful degradation compares against
        ``policy.storm_threshold``."""
        return self.churn_snapshot()[1]

    # -- relocation plane (hot-zone evacuation) ------------------------------
    def relocate(self, now: float) -> int:
        """One relocation pass: evacuate up to ``policy.relocate_budget``
        of the highest-expected-loss preemptible instances from every ARMED
        hot zone, checkpoint → place → kill, never the reverse.

        Hysteresis: a zone arms when its learned churn ẑ crosses
        ``policy.relocate_threshold`` (outside its cooldown window) and
        disarms — entering a ``relocate_cooldown_s`` cooldown — when ẑ
        falls below ``policy.relocate_exit_threshold``.  Failed
        re-placements leave their victim running and push the zone's
        ``retry_at`` out exponentially (``relocate_backoff_s`` doubling per
        consecutive failure).

        Re-placements go through the ordinary decision pipeline with the
        source zone hard-excluded (``Request.exclude_zone``); with the
        admission plane on they ride the queue as class-0 preemptible
        entries and settle asynchronously at the drain that decides them.
        Returns the number of evacuations initiated this pass."""
        pol = self.policy
        if not pol.relocation_on:
            raise RuntimeError(
                "relocation plane is off; build the fleet with "
                "SchedulerPolicy(relocate_threshold=...)"
            )
        st = self.relocation
        st.passes += 1
        rates, _ = self.churn_snapshot()
        started = 0
        for zone in self.zone_ids:
            z = self._reloc_zone.setdefault(zone, _ZoneReloc())
            rate = rates[zone]
            if z.armed and rate < pol.relocate_exit_threshold:
                z.armed = False
                z.cooldown_until = now + pol.relocate_cooldown_s
                st.disarms += 1
            elif (
                not z.armed
                and rate > pol.relocate_threshold
                and now >= z.cooldown_until
            ):
                z.armed = True
                z.fail_streak = 0
                z.retry_at = float("-inf")
                st.arms += 1
            if z.armed and now >= z.retry_at:
                started += self._evacuate_zone(zone, now)
        return started

    def _evacuate_zone(self, zone: str, now: float) -> int:
        """Evacuate one armed zone's worst-loss victims (≤ budget).

        Direct (unqueued) mode runs the whole batch as ONE fused
        ``relocate_many`` dispatch — per victim checkpoint → re-place →
        terminate in the exact sequence the old per-victim
        ``schedule_request`` loop applied, so decisions are bit-identical
        while the dispatch count drops from one per victim to one per zone
        (``tests/test_relocation.py`` pins both).  With the admission plane
        on, victims still ride the queue one entry each and settle at the
        drain that decides them."""
        pol = self.policy
        st = self.relocation
        budget = min(pol.relocate_budget, self.state.n_hosts * self.k_slots)
        hosts, slots, valid = _relocation_victims(
            self.state, jnp.int32(self.zone_ids[zone]), jnp.float32(now),
            jnp.float32(pol.period), budget=budget,
        )
        hosts, slots = np.asarray(hosts), np.asarray(slots)
        valid = np.asarray(valid)
        started = 0
        batch: List[Tuple[str, int, int, Instance, Request]] = []
        for h, s, v in zip(hosts, slots, valid):
            if not v:
                continue
            iid = self.slot_ids[int(h)][int(s)]
            assert iid is not None, "relocation victim slot empty in mirror"
            if iid in self._reloc_inflight:
                continue  # already mid-flight from an earlier pass
            inst = self.instances[iid]
            st.attempted += 1
            req = Request(
                id=f"reloc-{iid}",
                resources=inst.resources,
                preemptible=True,
                user=inst.user,
                cost_kind=inst.cost_kind,
                period=inst.period,
                priority=0,
                exclude_zone=zone,
                metadata={"relocation": iid},
            )
            if self.admission is not None:
                # Checkpoint FIRST: the replacement restarts from here, and
                # a storm racing the move loses only the work since now.
                self.checkpoint(iid, now)
                self.admission.submit_relocation(
                    req, iid, zone, now, price=inst.price_rate
                )
                self._reloc_inflight.add(iid)
                st.pending += 1
                started += 1
            else:
                # Mirror half of the checkpoint now; the device half runs
                # inside the fused scan (gated per row), keeping the
                # checkpoint→place→kill order per victim.
                inst.last_checkpoint = now
                batch.append((iid, int(h), int(s), inst, req))
        if batch:
            started += self._relocate_batch(zone, batch, now)
        return started

    def _relocate_batch(
        self,
        zone: str,
        batch: List[Tuple[str, int, int, Instance, Request]],
        now: float,
    ) -> int:
        """Direct-mode settle of one fused ``relocate_many`` dispatch."""
        b = len(batch)
        padded = max(4, 1 << (b - 1).bit_length())
        d = len(self.spec.dims)
        vh = np.zeros((padded,), np.int32)
        vs = np.zeros((padded,), np.int32)
        von = np.zeros((padded,), bool)
        res = np.full((padded, d), _PAD_RES, np.float32)
        dom = np.full((padded,), -1, np.int32)
        kind = np.full((padded,), -1, np.int32)
        period = np.full((padded,), -1.0, np.float32)
        price = np.ones((padded,), np.float32)
        excl = np.full((padded,), -1, np.int32)
        for i, (iid, h, s, inst, req) in enumerate(batch):
            (res[i], _, dom[i], kind[i], period[i],
             excl[i]) = self._req_arrays(req)
            vh[i], vs[i], von[i] = h, s, True
            price[i] = inst.price_rate
        self.state, (host_idx, slot, ok, fell_back, margin) = relocate_many(
            self.state, vh, vs, von, res, dom, kind, period, price, excl,
            now, policy=self._flush_policy(),
        )
        host_idx, slot = np.asarray(host_idx), np.asarray(slot)
        ok = np.asarray(ok)
        fb = np.asarray(fell_back)[:b]
        mg = np.asarray(margin)[:b]
        self._observe(int(fb.sum()), float(mg.min()), b)
        st = self.relocation
        z = self._reloc_zone.setdefault(zone, _ZoneReloc())
        no_kill = np.zeros((self.k_slots,), bool)
        started = 0
        for i, (iid, h, s, inst, req) in enumerate(batch):
            if bool(ok[i]):
                out = self._absorb(
                    req, now, inst.price_rate,
                    int(host_idx[i]), int(slot[i]), True, no_kill,
                )
                # The fused scan already departed the victim on device
                # (make-before-break, voluntary); fold the mirror here —
                # the python half of ``_settle_relocation_placed`` minus
                # the device transition.  Direct mode is single-threaded,
                # so the lost/stale races of the queued path cannot occur.
                self.instances.pop(iid)
                del self.locator[iid]
                self.slot_ids[h][s] = None
                self.relocated_ids[iid] = out.instance.id
                st.relocated += 1
                z.fail_streak = 0
                started += 1
            else:
                self._settle_relocation_rejected(iid, zone, now)
        return started

    def _settle_relocation_placed(
        self, victim_id: str, zone: str, out: SoAOutcome, now: float
    ) -> None:
        """Make-before-break settle: the replacement is live, so the victim
        (if still running) departs — voluntarily: a move is not churn, so
        the source zone's ẑ numerator is untouched."""
        st = self.relocation
        if victim_id in self._reloc_inflight:
            self._reloc_inflight.discard(victim_id)
            st.pending -= 1
        z = self._reloc_zone.setdefault(zone, _ZoneReloc())
        if victim_id in self.instances:
            self.depart(victim_id, now=now)
            self.relocated_ids[victim_id] = out.instance.id
            st.relocated += 1
            z.fail_streak = 0
        elif any(i.id == victim_id for i in self.preempted):
            # The storm beat the move: the victim is already dead, and the
            # replacement stands as its restore from the checkpoint taken
            # at evacuation time.
            self.relocated_ids[victim_id] = out.instance.id
            st.lost_victims += 1
        else:
            # Victim departed on its own mid-flight: the replacement is
            # surplus — drop it immediately (no duplicate, no double bill).
            self.depart(out.instance.id, now=now)
            st.stale += 1

    def _settle_relocation_rejected(
        self, victim_id: str, zone: str, now: float
    ) -> None:
        """Never-worse: a failed re-placement leaves the victim running and
        backs the zone off exponentially."""
        st = self.relocation
        if victim_id in self._reloc_inflight:
            self._reloc_inflight.discard(victim_id)
            st.pending -= 1
        st.failed += 1
        z = self._reloc_zone.setdefault(zone, _ZoneReloc())
        z.fail_streak += 1
        z.retry_at = now + self.policy.relocate_backoff_s * (
            2.0 ** (z.fail_streak - 1)
        )

    def checkpoint(self, instance_id: str, now: float) -> bool:
        """Record a durable checkpoint for a live preemptible instance (its
        recompute cost restarts from ``now``).  Returns False when the
        instance is gone or not preemptible — checkpoints are idempotent."""
        loc = self.locator.get(instance_id)
        if loc is None or loc[1] is None:
            return False
        host_idx, slot = loc
        self.instances[instance_id].last_checkpoint = now
        self.state = apply_checkpoint(self.state, host_idx, slot, now)
        return True

    def heal_host(self, name: str) -> None:
        self.state = set_schedulable(self.state, self.index[name], True)

    def set_slow(self, name: str, slow_factor: float) -> None:
        self.state = set_slow_factor(self.state, self.index[name], slow_factor)

    # -- python-object sync (sample points / verification only) --------------
    def slot_assignment(self) -> List[Dict[str, int]]:
        """Per-host id → slot map, for bit-exact oracle rebuilds."""
        return [
            {iid: k for k, iid in enumerate(row) if iid is not None}
            for row in self.slot_ids
        ]

    def sync_hosts(self) -> List[Host]:
        """Materialize python ``Host`` objects from the mirror records.

        Placement goes through ``Host.place`` so capacity violations in the
        incremental state surface here as hard errors."""
        schedulable = np.asarray(self.state.schedulable)
        slow = np.asarray(self.state.slow)
        hosts = [
            Host(
                name=self.names[i],
                capacity=self.capacity[i],
                domain=self.domains[i],
                zone=self.zones[i],
                schedulable=bool(schedulable[i]),
                slow_factor=float(slow[i]),
            )
            for i in range(self.n_hosts)
        ]
        for inst in self.instances.values():
            host_idx, _ = self.locator[inst.id]
            hosts[host_idx].place(inst)
        return hosts
