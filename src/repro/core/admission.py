"""Streaming admission front end: device-resident wait queue + drain plane.

Every decision path below this module is one-shot: a request arrives, the
pipeline decides, and a rejection simply vanishes.  Real fleets live under
*continuous* demand — the paper's scheduler exists to keep an IaaS fleet full
— so this module adds the missing admission plane in front of the decision
pipeline:

* **Device-resident wait queue** (``AdmissionQueueState``): a fixed-capacity
  struct-of-arrays queue living next to ``SoAFleetState``.  Each entry
  carries the request's resource vector, flags, a **priority class** (0 =
  interactive, highest; ``n_classes - 1`` = batch, lowest), a monotone FIFO
  ticket (``seq``), its enqueue time, and a retry counter.  All transitions
  (``queue_push`` / ``queue_select`` / ``queue_pop``) are pure jnp — the
  queue never leaves the device between drains.
* **Drains** (``drain_queue`` / the fused ``_drain_entry``): one dispatch
  pushes the newly-accumulated arrivals, selects the top ``admit_batch``
  waiting entries by ``(class, seq)`` — strict priority order, FIFO within a
  class — runs them through the exact ``schedule_many`` scan body
  (``jax_scheduler._step_core``), and folds the outcomes back: placed
  entries leave the queue, failed entries stay for **backfill retry** (their
  ``tries`` counter increments; ``max_retries`` attempts total before the
  request is rejected).  Because the drain feeds the identical per-request
  arrays through the identical scan body, a drained queue's decisions are
  bit-exact against the unqueued oracle (tests/test_admission.py).
* **Interactive preempts batch** by construction, not by new machinery:
  interactive requests are the normal (non-preemptible) ones, so the
  existing preemption predicate in ``_decision_core`` — normal requests may
  evacuate preemptible instances — IS the cross-class preemption.  The
  queue adds the ordering half (interactive drains first); the decision
  pipeline supplies the eviction half unchanged.
* **Async double-buffered dispatch** (``AdmissionFrontEnd``): arrivals
  accumulate host-side into the next batch while the previous drain's
  device program is still running; JAX's async dispatch returns
  immediately, and because every transition donates its input buffers the
  in-place state update is safe.  Outcome absorption (the only host sync)
  is deferred until the result is actually needed — the next drain, a
  state-observing simulator event, or a stats read.

SLO discipline: the front end accumulates arrivals toward
``policy.admit_batch`` (throughput), but a drain is forced once the oldest
waiting arrival has waited ``policy.slo_target_s`` sim-seconds (latency).
``SoASimulator`` drives both triggers plus a third: a drain after any
capacity-freeing event (departure / host failure) while the queue is
non-empty — the backfill path.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import obs
from .jax_scheduler import SoAFleetState, _scan_live_rows, _step_core
from .policy import COST_KIND_IDS, SchedulerPolicy
from .screen_math import POS_INF, churn_stats
from .types import Request

#: Padding sentinel: a request no host can fit, so ``_step_core`` no-ops it
#: (``ok=False``).  A drain's untaken rows carry it too, though its loop
#: never runs them.  Same value as ``soa_fleet._PAD_RES`` (which
#: re-exports this one).
PAD_RES = 1e30

#: Sort key for invalid queue entries — larger than any real class or seq,
#: so they sink to the back of every selection.
_BIG = jnp.int32(2**30)


# ---------------------------------------------------------------------------
# Queue state + pure transitions
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AdmissionQueueState:
    """Fixed-capacity device-resident wait queue (struct-of-arrays).

    ``Q = policy.queue_capacity`` rows; a row is live iff ``valid``.  The
    ``(klass, seq)`` pair is the total drain order: strict priority by
    class, FIFO by the monotone ``seq`` ticket within a class.  ``tries``
    counts placement attempts already consumed (backfill retries).
    """

    res: jax.Array          # (Q, D) f32 request resource vectors
    preemptible: jax.Array  # (Q,)   bool
    domain: jax.Array       # (Q,)   i32; -1 = any
    cost_kind: jax.Array    # (Q,)   i32 kind id; -1 = policy default
    period: jax.Array       # (Q,)   f32 contract period; -1 = policy default
    exclude_zone: jax.Array  # (Q,)  i32 hard-excluded zone id; -1 = none
    klass: jax.Array        # (Q,)   i32 priority class; 0 = highest
    price: jax.Array        # (Q,)   f32
    enq_t: jax.Array        # (Q,)   f32 enqueue (arrival) time
    seq: jax.Array          # (Q,)   i32 FIFO ticket
    tries: jax.Array        # (Q,)   i32 failed placement attempts so far
    valid: jax.Array        # (Q,)   bool
    next_seq: jax.Array     # ()     i32 ticket counter

    @property
    def capacity(self) -> int:
        return self.res.shape[0]

    @property
    def depth(self) -> jax.Array:
        """Live entries (traced; host callers use the drain aux instead)."""
        return jnp.sum(self.valid).astype(jnp.int32)


def queue_init(capacity: int, n_dims: int) -> AdmissionQueueState:
    """Empty queue with ``capacity`` rows over ``n_dims`` resource dims."""
    q = int(capacity)
    return AdmissionQueueState(
        res=jnp.zeros((q, n_dims), jnp.float32),
        preemptible=jnp.zeros((q,), bool),
        domain=jnp.full((q,), -1, jnp.int32),
        cost_kind=jnp.full((q,), -1, jnp.int32),
        period=jnp.full((q,), -1.0, jnp.float32),
        exclude_zone=jnp.full((q,), -1, jnp.int32),
        klass=jnp.zeros((q,), jnp.int32),
        price=jnp.ones((q,), jnp.float32),
        enq_t=jnp.zeros((q,), jnp.float32),
        seq=jnp.zeros((q,), jnp.int32),
        tries=jnp.zeros((q,), jnp.int32),
        valid=jnp.zeros((q,), bool),
        next_seq=jnp.int32(0),
    )


def queue_push(
    q: AdmissionQueueState,
    res: jax.Array,          # (D,)
    preemptible: jax.Array,  # () bool
    domain: jax.Array,       # () i32
    cost_kind: jax.Array,    # () i32
    period: jax.Array,       # () f32; -1 = policy default
    exclude_zone: jax.Array,  # () i32; -1 = none
    klass: jax.Array,        # () i32
    enq_t: jax.Array,        # () f32
    price: jax.Array,        # () f32
    live: jax.Array = True,  # () bool — False = padding row, no-op
) -> Tuple[AdmissionQueueState, jax.Array, jax.Array]:
    """Enqueue one arrival into the first free row.

    Returns ``(q', slot, ok)``; ``ok=False`` (queue full, or ``live=False``)
    leaves the queue untouched — a full queue REJECTS at arrival, it never
    displaces a waiting entry.
    """
    free = ~q.valid
    ok = jnp.asarray(live) & jnp.any(free)
    slot = jnp.argmax(free).astype(jnp.int32)
    sel = (jnp.arange(q.capacity) == slot) & ok
    q = dataclasses.replace(
        q,
        res=jnp.where(sel[:, None], jnp.asarray(res, jnp.float32)[None, :], q.res),
        preemptible=jnp.where(sel, preemptible, q.preemptible),
        domain=jnp.where(sel, jnp.asarray(domain, jnp.int32), q.domain),
        cost_kind=jnp.where(sel, jnp.asarray(cost_kind, jnp.int32), q.cost_kind),
        period=jnp.where(sel, jnp.asarray(period, jnp.float32), q.period),
        exclude_zone=jnp.where(
            sel, jnp.asarray(exclude_zone, jnp.int32), q.exclude_zone
        ),
        klass=jnp.where(sel, jnp.asarray(klass, jnp.int32), q.klass),
        price=jnp.where(sel, jnp.asarray(price, jnp.float32), q.price),
        enq_t=jnp.where(sel, jnp.asarray(enq_t, jnp.float32), q.enq_t),
        seq=jnp.where(sel, q.next_seq, q.seq),
        tries=jnp.where(sel, 0, q.tries),
        valid=q.valid | sel,
        next_seq=q.next_seq + ok.astype(jnp.int32),
    )
    return q, slot, ok


def queue_select(
    q: AdmissionQueueState,
    batch: int,
    now: Optional[jax.Array] = None,
    aging_rate=0.0,
    n_classes: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Pick the next ``batch`` entries in drain order.

    Order is ``(klass asc, seq asc)`` — strict priority between classes,
    FIFO within a class; retries keep their original ticket, so a failed
    entry re-drains ahead of everything that arrived after it.  Returns
    ``(idx (B,), take (B,))``; rows with ``take=False`` gathered an invalid
    entry (queue shorter than the batch) and must be treated as padding.

    The two-key order is computed as ONE stable sort over a packed monotone
    uint32 key — effective class in the high ``cb = n_classes.bit_length()``
    bits, ``seq`` below, invalid rows pinned to the all-ones sentinel — so
    every drain pays a single sort pass instead of ``lexsort``'s two.  The
    packing is exact (bit-identical to the old lexsort order, pinned by
    tests/test_admission.py) because a valid key can never collide with the
    sentinel: classes are clipped to ``2**cb - 2`` and ``seq`` tickets must
    stay below ``2**(32 - cb)`` (~10^9 at the default two classes; callers
    with ``n_classes=None`` get an 8-bit class field and 2^24 tickets).

    With ``aging_rate > 0`` (``policy.aging_rate``, or a TRACED scalar on
    the scanned simulator's knob axis) an entry's *effective* class decays
    with its queue wait — ``max(0, klass - floor(aging_rate * (now -
    enq_t)))`` — so long-waiting batch entries eventually drain ahead of
    fresh interactive load instead of starving (and stop burning retries
    against a fleet that keeps serving class 0 first).  The secondary
    ``seq`` key is untouched: FIFO within an effective class, and
    ``aging_rate=0`` (static or traced) selects exactly the pre-aging
    order.
    """
    klass = q.klass
    if now is not None and (isinstance(aging_rate, jax.Array) or aging_rate):
        waited = jnp.maximum(jnp.asarray(now, jnp.float32) - q.enq_t, 0.0)
        decay = jnp.floor(
            jnp.asarray(aging_rate, jnp.float32) * waited
        ).astype(jnp.int32)
        klass = jnp.maximum(klass - decay, 0)
    cb = int(n_classes).bit_length() if n_classes else 8
    shift = 32 - cb
    packed = (
        jnp.clip(klass, 0, (1 << cb) - 2).astype(jnp.uint32) << shift
    ) | q.seq.astype(jnp.uint32)
    key = jnp.where(q.valid, packed, jnp.uint32(0xFFFFFFFF))
    order = jnp.argsort(key, stable=True)
    idx = order[: int(batch)].astype(jnp.int32)
    return idx, q.valid[idx]


def queue_pop(
    q: AdmissionQueueState,
    idx: jax.Array,     # (B,) rows a drain attempted
    take: jax.Array,    # (B,) which of them were real
    placed: jax.Array,  # (B,) which of those the pipeline placed
    max_retries: int,
) -> Tuple[AdmissionQueueState, jax.Array]:
    """Fold one drain's outcomes back into the queue.

    Placed entries leave; failed entries burn one retry and stay (backfill)
    until ``max_retries`` attempts are exhausted, at which point they are
    dropped.  Returns ``(q', dropped (B,))``.
    """
    fail = take & ~placed
    tries_new = q.tries[idx] + fail.astype(jnp.int32)
    dropped = fail & (tries_new >= int(max_retries))
    remove = placed | dropped
    q = dataclasses.replace(
        q,
        tries=q.tries.at[idx].set(jnp.where(take, tries_new, q.tries[idx])),
        valid=q.valid.at[idx].set(
            jnp.where(take, q.valid[idx] & ~remove, q.valid[idx])
        ),
    )
    return q, dropped


# ---------------------------------------------------------------------------
# The fused drain: push arrivals → select → decide (scan) → pop
# ---------------------------------------------------------------------------


def _drain_entry(
    fleet_state: SoAFleetState,
    q: AdmissionQueueState,
    new_res,     # (A, D) arrival buffer (padded)
    new_pre,     # (A,) bool
    new_dom,     # (A,) i32
    new_kind,    # (A,) i32
    new_period,  # (A,) f32; -1 = policy default
    new_excl,    # (A,) i32 excluded zone id; -1 = none
    new_cls,     # (A,) i32
    new_t,       # (A,) f32 arrival times
    new_price,   # (A,) f32
    new_live,    # (A,) bool — padding rows False
    now,         # () f32 drain time
    *,
    policy: SchedulerPolicy,
):
    """One admission drain, fully fused (one dispatch).

    Decisions run through the exact ``schedule_many`` scan body at a common
    ``now`` (the drain time), so a drained queue is bit-exact against
    feeding the same requests to the unqueued pipeline in drain order.
    Only the taken rows run: they are a prefix of the batch, and the
    ``decide`` loop stops after them (``_scan_live_rows``).  A row it skips
    reads ``host_idx`` and ``slot`` -1, ``ok``, ``kill`` and ``fell_back``
    False, and ``margin`` ``POS_INF`` (a decision with no candidate).

    Graceful degradation (``policy.storm_threshold``): when the fleet-wide
    observed churn rate ΣT/max(ΣU, eps) — read off the state's zone
    accumulators — exceeds the threshold, this drain's preemptible rows are
    demoted to non-preemptible *for this attempt* (spot capacity is being
    reclaimed fleet-wide, so handing out more spot placements just feeds
    the storm).  The demotion is reported per row (``degraded``) so the
    host mirror books the placement under the demoted request.
    """

    def push_body(qs, xs):
        qs, slot, ok = queue_push(qs, *xs)
        return qs, (slot, ok)

    with jax.named_scope("queue_push"):
        q, (new_slot, pushed) = jax.lax.scan(
            push_body, q,
            (new_res, new_pre, new_dom, new_kind, new_period, new_excl,
             new_cls, new_t, new_price, new_live),
        )

    with jax.named_scope("queue_select"):
        idx, take = queue_select(
            q, policy.admit_batch, now=now, aging_rate=policy.aging_rate,
            n_classes=policy.n_classes,
        )
        b = idx.shape[0]
        b_res = jnp.where(take[:, None], q.res[idx], PAD_RES)
        b_pre = jnp.where(take, q.preemptible[idx], False)
        b_dom = jnp.where(take, q.domain[idx], -1)
        b_kind = jnp.where(take, q.cost_kind[idx], -1)
        b_period = jnp.where(take, q.period[idx], -1.0)
        b_excl = jnp.where(take, q.exclude_zone[idx], -1)
        b_price = jnp.where(take, q.price[idx], 1.0)
        b_now = jnp.full((b,), now, jnp.float32)

    if policy.storm_threshold is not None:
        # fleet-wide rate = last entry of the shared fused churn reduction
        churn = churn_stats(fleet_state.zone_term, fleet_state.zone_up)[-1]
        storm = churn > jnp.float32(policy.storm_threshold)
        degraded = b_pre & storm
        b_pre = b_pre & ~storm
    else:
        degraded = jnp.zeros_like(b_pre)

    # The exclusion operand rides the scan only when the relocation plane is
    # on, so relocation-off policies compile the exact pre-relocation drain.
    excl_xs = b_excl if policy.relocation_on else jnp.full((b,), -1, jnp.int32)

    def body(st, xs):
        res, pre, dom, t, price, kind, period, excl = xs
        return _step_core(
            st, res, pre, dom, t, price, kind, period, policy,
            req_exclude=excl if policy.relocation_on else None,
        )

    with jax.named_scope("decide"):
        fleet_state, (host_idx, slot, ok, kill, fell_back, margin) = (
            _scan_live_rows(
                body, fleet_state,
                (b_res, b_pre, b_dom, b_now, b_price, b_kind, b_period,
                 excl_xs),
                take,
                (jnp.int32(-1), jnp.int32(-1), jnp.bool_(False),
                 jnp.zeros((fleet_state.k_slots,), bool), jnp.bool_(False),
                 jnp.float32(POS_INF)),
            )
        )
    with jax.named_scope("queue_pop"):
        placed = ok & take
        wait = jnp.where(placed, now - q.enq_t[idx], 0.0)
        q, dropped = queue_pop(q, idx, take, placed, policy.max_retries)
    return fleet_state, q, (
        new_slot, pushed, idx, take, placed, host_idx, slot, kill,
        fell_back, margin, wait, dropped, degraded, q.depth,
    )


_DRAIN_STATICS = ("policy",)
_drain_donated = functools.partial(
    jax.jit, static_argnames=_DRAIN_STATICS, donate_argnums=(0, 1)
)(_drain_entry)
_drain_kept = functools.partial(
    jax.jit, static_argnames=_DRAIN_STATICS
)(_drain_entry)


# ---------------------------------------------------------------------------
# Host-side mirror: stats, identity bookkeeping, async dispatch
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AdmissionStats:
    """Counters + latency samples of one front end (host-side).

    Conservation invariant (pinned by tests/test_admission.py): every
    arrival is in exactly one bucket —
    ``arrivals == admitted + rejected_overflow + rejected_retry
    + queue_depth + pending``; and every attempt is exactly one outcome —
    ``attempts == admitted + retries + rejected_retry``.

    The per-request wall samples (``queue_wall_s`` .. ``refused``) hold one
    entry per request decided by placement or by refusal after retries, in
    the order decided.  Their three segments add up to the request's
    submit → outputs-fetched time (its ``wall_wait_s`` entry when placed).
    """

    arrivals: int = 0
    admitted: int = 0
    rejected_overflow: int = 0
    rejected_retry: int = 0
    drains: int = 0
    retries: int = 0
    #: preemptible attempts demoted to non-preemptible by storm degradation
    degraded: int = 0
    queue_depth: int = 0
    #: drain rows that held a request (admitted, retried or refused)
    attempts: int = 0
    #: attempts whose decision ran the full-enumeration fallback
    fallbacks: int = 0
    #: sim-time admission latency (drain time - arrival time) per placement
    wait_s: List[float] = dataclasses.field(default_factory=list)
    #: wall-clock submit → outcome-absorbed latency per placement (seconds)
    wall_wait_s: List[float] = dataclasses.field(default_factory=list)
    #: submit → dispatch of the first drain that attempted the request
    queue_wall_s: List[float] = dataclasses.field(default_factory=list)
    #: that first attempt's dispatch → dispatch of the deciding drain
    retry_wall_s: List[float] = dataclasses.field(default_factory=list)
    #: deciding drain's dispatch → its outputs fetched to the host
    fetch_wall_s: List[float] = dataclasses.field(default_factory=list)
    #: attempts the request took (1 .. ``max_retries``)
    tries: List[int] = dataclasses.field(default_factory=list)
    #: True where the request was refused after retries, False where placed
    refused: List[bool] = dataclasses.field(default_factory=list)

    @property
    def rejected(self) -> int:
        return self.rejected_overflow + self.rejected_retry

    def _decided(self, w: "_Waiting", dispatch: float, fetched: float,
                 refused: bool) -> None:
        """Record the wall samples of request ``w``, decided by the drain
        dispatched at ``dispatch`` whose outputs were fetched at ``fetched``
        (``time.perf_counter()`` stamps)."""
        self.queue_wall_s.append(w.first_dispatch - w.submit_wall)
        self.retry_wall_s.append(dispatch - w.first_dispatch)
        self.fetch_wall_s.append(fetched - dispatch)
        self.tries.append(w.tries)
        self.refused.append(refused)

    @staticmethod
    def _pct(samples: Sequence[float], pct: float) -> float:
        if not samples:
            return 0.0
        # f32 on purpose: the waits themselves are f32 device differences,
        # and interpolating in f32 keeps this reader bit-identical to the
        # scanned engine's (``ScanResult.wait_percentiles``).
        return float(np.percentile(np.asarray(samples, np.float32), pct))

    def wait_percentiles(self) -> Dict[str, float]:
        """Sim-time queue-wait distribution (drain time − arrival time per
        admitted placement).  The waits are f32 differences computed by the
        device drain program itself, so the same reader over
        ``ScanResult.wait_s`` (the in-carry accumulator of the scanned
        simulator) returns bit-identical percentiles — the deterministic
        latency comparison the streaming parity suite pins."""
        return {
            "wait_p50_s": self._pct(self.wait_s, 50),
            "wait_p99_s": self._pct(self.wait_s, 99),
        }

    def summary(self) -> Dict[str, float]:
        return {
            "arrivals": self.arrivals,
            "admitted": self.admitted,
            "rejected_overflow": self.rejected_overflow,
            "rejected_retry": self.rejected_retry,
            "drains": self.drains,
            "retries": self.retries,
            "degraded": self.degraded,
            "queue_depth": self.queue_depth,
            "attempts": self.attempts,
            "fallbacks": self.fallbacks,
            "wait_p50_s": self._pct(self.wait_s, 50),
            "wait_p99_s": self._pct(self.wait_s, 99),
            "wall_p50_us": self._pct(self.wall_wait_s, 50) * 1e6,
            "wall_p99_us": self._pct(self.wall_wait_s, 99) * 1e6,
        }


@dataclasses.dataclass(frozen=True)
class DrainResult:
    """Host-side view of one absorbed drain."""

    now: float
    #: every attempted (request, placed) pair in service (drain) order —
    #: the exact decision sequence, for oracle replays
    attempts: Tuple[Tuple[Request, bool], ...]
    #: placed requests' outcomes, in service (drain) order
    outcomes: Tuple[object, ...]          # Tuple[SoAOutcome, ...]
    #: requests rejected by this drain (queue overflow or retries exhausted)
    rejected: Tuple[Request, ...]
    #: requests that failed placement but remain queued for backfill retry
    retried: Tuple[Request, ...]
    #: live queue entries after the drain
    queue_depth: int


@dataclasses.dataclass
class _Waiting:
    """One not-yet-admitted request (host mirror of a queue row)."""

    request: Request
    price: float
    klass: int
    enq_t: float
    submit_wall: float  # time.perf_counter() at submit
    #: time.perf_counter() at the dispatch of the first drain attempting it
    first_dispatch: Optional[float] = None
    tries: int = 0      # drains that attempted it so far


class AdmissionFrontEnd:
    """Async admission layer over one ``SoAFleet``.

    Arrivals ``submit()`` into a host-side accumulation buffer; ``drain()``
    flushes buffer + queue through the fused drain program.  With
    ``block=False`` the dispatch returns immediately (double-buffering:
    the host accumulates the next batch while the device runs this one);
    outcomes are absorbed lazily on the next drain / ``flush()`` / stats
    read.  The owning fleet's python mirror is updated through the same
    ``_absorb`` path as the direct entry points, so departures, failures
    and oracle rebuilds compose unchanged.
    """

    def __init__(self, fleet):
        policy = fleet.policy
        if policy.queue_capacity <= 0:
            raise ValueError(
                "AdmissionFrontEnd needs policy.queue_capacity > 0"
            )
        if policy.mesh is not None:
            raise NotImplementedError(
                "admission queue + sharded fleet state is future work; "
                "drop policy.mesh or policy.queue_capacity"
            )
        self.fleet = fleet
        self.policy = policy
        self.qstate = queue_init(policy.queue_capacity, len(fleet.spec.dims))
        #: queue row → waiting record (mirrors ``AdmissionQueueState.valid``)
        self.slots: List[Optional[_Waiting]] = [None] * policy.queue_capacity
        self._pending: List[_Waiting] = []
        #: relocation re-placements in flight: request id → (victim id,
        #: source zone).  The owning fleet settles each entry at the drain
        #: that decides it (make-before-break; see ``SoAFleet.relocate``).
        self._reloc: Dict[str, Tuple[str, str]] = {}
        self._inflight = None
        #: results absorbed as a side effect (a blocking drain flushing a
        #: previous non-blocking one) awaiting ``take_results``
        self._unclaimed: List[DrainResult] = []
        self.stats = AdmissionStats()

    # -- submission -----------------------------------------------------------
    def _klass_of(self, req: Request) -> int:
        nc = self.policy.n_classes
        if req.priority is None:
            return 0 if not req.preemptible else nc - 1
        k = int(req.priority)
        if not 0 <= k < nc:
            raise ValueError(
                f"request {req.id} priority {k} outside the policy's "
                f"{nc} classes"
            )
        return k

    def submit(self, req: Request, now: float, price: float = 1.0) -> None:
        """Accept one arrival into the accumulation buffer (never blocks)."""
        with obs.span(obs.SUBMIT):
            self.fleet._req_arrays(req)  # validate cost kind early
            self._pending.append(
                _Waiting(
                    request=req, price=float(price),
                    klass=self._klass_of(req), enq_t=float(now),
                    submit_wall=time.perf_counter(),
                )
            )
            self.stats.arrivals += 1

    def submit_relocation(
        self, req: Request, victim_id: str, zone: str, now: float,
        price: float = 1.0,
    ) -> None:
        """Queue one relocation re-placement.  It rides the queue as a
        class-0 entry (drains with interactive traffic) but stays
        preemptible, so it can never displace user placements.  The victim
        keeps running until the drain that places this entry settles it
        (``SoAFleet._settle_relocation_placed``); a rejected entry leaves
        the victim untouched and backs the zone off."""
        self.submit(req, now, price=price)
        self._reloc[req.id] = (victim_id, zone)

    @property
    def pending(self) -> int:
        """Arrivals accumulated but not yet pushed to the device queue."""
        return len(self._pending)

    @property
    def waiting(self) -> int:
        """Everything not yet decided: buffer + live queue entries."""
        return len(self._pending) + sum(w is not None for w in self.slots)

    def batch_ready(self) -> bool:
        return len(self._pending) >= self.policy.admit_batch

    def oldest_enq_t(self) -> Optional[float]:
        ts = [w.enq_t for w in self._pending]
        ts += [w.enq_t for w in self.slots if w is not None]
        return min(ts) if ts else None

    def next_deadline(self) -> Optional[float]:
        """Sim time by which the SLO forces the next drain (None = idle)."""
        oldest = self.oldest_enq_t()
        return None if oldest is None else oldest + self.policy.slo_target_s

    # -- drains ---------------------------------------------------------------
    def drain(self, now: float, block: bool = True) -> Optional[DrainResult]:
        """Dispatch one drain at sim time ``now``.

        Absorbs any in-flight previous drain first (ordering; its result
        lands in ``take_results``), then pushes the pending buffer + runs
        one ``admit_batch`` selection.  Returns this drain's
        ``DrainResult`` when ``block``; with ``block=False`` returns None
        immediately and the result is absorbed later (``flush`` /
        ``take_results``).
        """
        self.sync()
        pend, self._pending = self._pending, []
        if not pend and not any(w is not None for w in self.slots):
            return DrainResult(
                now=float(now), attempts=(), outcomes=(), rejected=(),
                retried=(), queue_depth=0,
            ) if block else None

        with obs.span(obs.PACK):
            a = max(4, 1 << (len(pend) - 1).bit_length()) if pend else 4
            d = len(self.fleet.spec.dims)
            res = np.full((a, d), PAD_RES, np.float32)
            pre = np.zeros((a,), bool)
            dom = np.full((a,), -1, np.int32)
            kind = np.full((a,), -1, np.int32)
            per = np.full((a,), -1.0, np.float32)
            exc = np.full((a,), -1, np.int32)
            cls = np.zeros((a,), np.int32)
            enq = np.zeros((a,), np.float32)
            price = np.ones((a,), np.float32)
            live = np.zeros((a,), bool)
            for i, w in enumerate(pend):
                r, p, dm, kd, pd, ex = self.fleet._req_arrays(w.request)
                res[i], pre[i], dom[i], kind[i], per[i], exc[i] = (
                    r, p, dm, kd, pd, ex
                )
                cls[i], enq[i], price[i], live[i] = (
                    w.klass, w.enq_t, w.price, True
                )

        seq = self.stats.drains
        with obs.span(obs.DISPATCH, drain=seq):
            dispatched = time.perf_counter()
            policy = self.fleet._flush_policy()
            fn = _drain_donated if policy.donate else _drain_kept
            self.fleet.state, self.qstate, aux = fn(
                self.fleet.state, self.qstate,
                res, pre, dom, kind, per, exc, cls, enq, price, live,
                jnp.asarray(now, jnp.float32), policy=policy,
            )
        self._inflight = (pend, float(now), aux, seq, dispatched)
        self.stats.drains += 1
        return self.flush() if block else None

    def flush(self) -> Optional[DrainResult]:
        """Absorb the in-flight drain's outcomes (blocks on the device)."""
        if self._inflight is None:
            return None
        pend, now, aux, seq, dispatched = self._inflight
        self._inflight = None
        with obs.span(obs.FETCH, drain=seq):
            (new_slot, pushed, idx, take, placed, host_idx, slot, kill,
             fell_back, margin, wait, dropped, degraded, depth) = (
                np.asarray(x) for x in aux
            )
        wall_now = time.perf_counter()
        with obs.span(obs.MIRROR):
            rejected: List[Request] = []
            # 1. arrivals → queue rows (or instant overflow rejection)
            for i, w in enumerate(pend):
                if pushed[i]:
                    self.slots[int(new_slot[i])] = w
                else:
                    self.stats.rejected_overflow += 1
                    rejected.append(w.request)
                    reloc = self._reloc.pop(w.request.id, None)
                    if reloc is not None:  # overflow: victim keeps running
                        self.fleet._settle_relocation_rejected(
                            reloc[0], reloc[1], now
                        )
            # 2. attempted rows, in service order
            outcomes, retried, attempts = [], [], []
            for j in range(len(idx)):
                if not take[j]:
                    continue
                row = int(idx[j])
                w = self.slots[row]
                assert w is not None, "drained an empty queue row"
                if w.first_dispatch is None:
                    w.first_dispatch = dispatched
                w.tries += 1
                # Storm degradation demoted this attempt on device; mirror
                # the demotion so the python bookkeeping matches what ran.
                req = w.request
                if degraded[j]:
                    req = dataclasses.replace(req, preemptible=False)
                    self.stats.degraded += 1
                attempts.append((req, bool(placed[j])))
                if placed[j]:
                    self.slots[row] = None
                    out = self.fleet._absorb(
                        req, now, w.price, int(host_idx[j]), int(slot[j]),
                        True, kill[j],
                    )
                    outcomes.append(out)
                    self.stats.admitted += 1
                    self.stats.wait_s.append(float(wait[j]))
                    self.stats.wall_wait_s.append(wall_now - w.submit_wall)
                    self.stats._decided(w, dispatched, wall_now, False)
                    reloc = self._reloc.pop(req.id, None)
                    if reloc is not None:  # make-before-break: the
                        # replacement is live — NOW the victim may die.
                        self.fleet._settle_relocation_placed(
                            reloc[0], reloc[1], out, now
                        )
                elif dropped[j]:
                    self.slots[row] = None
                    self.stats.rejected_retry += 1
                    self.stats._decided(w, dispatched, wall_now, True)
                    rejected.append(w.request)
                    reloc = self._reloc.pop(req.id, None)
                    if reloc is not None:  # victim keeps running; the
                        # zone backs off
                        self.fleet._settle_relocation_rejected(
                            reloc[0], reloc[1], now
                        )
                else:
                    self.stats.retries += 1
                    retried.append(w.request)
            n_take = int(take.sum())
            if n_take:
                fb = fell_back[take]
                mg = margin[take]
                self.fleet._observe(int(fb.sum()), float(mg.min()), n_take)
                self.stats.attempts += n_take
                self.stats.fallbacks += int(fb.sum())
            self.stats.queue_depth = int(depth)
            return DrainResult(
                now=now, attempts=tuple(attempts), outcomes=tuple(outcomes),
                rejected=tuple(rejected), retried=tuple(retried),
                queue_depth=int(depth),
            )

    def sync(self) -> None:
        """Absorb any in-flight drain, banking its result for
        ``take_results`` (safe to call anywhere the python mirror must be
        current — e.g. before a departure/failure event)."""
        prev = self.flush()
        if prev is not None:
            self._unclaimed.append(prev)

    def wait_percentiles(self) -> Dict[str, float]:
        """Sim-time queue-wait p50/p99 over every absorbed placement —
        the same reader ``ScanResult.wait_percentiles`` exposes for the
        scanned engine (bit-identical on a shared trace)."""
        self.sync()
        return self.stats.wait_percentiles()

    def take_results(self) -> List[DrainResult]:
        """Flush and return every drain result not yet handed to a caller
        (the non-blocking consumption pattern — see ``SoASimulator``)."""
        self.sync()
        out, self._unclaimed = self._unclaimed, []
        return out

    def drain_all(self, now: float) -> List[DrainResult]:
        """Drain until the queue is empty or every waiting entry has
        exhausted its retries (end-of-run / test epilogue)."""
        results: List[DrainResult] = []
        # Each failing entry burns one retry per drain, so this terminates
        # within ceil(Q/B) * max_retries + 1 rounds.
        cap = self.policy.queue_capacity
        limit = (
            -(-cap // self.policy.admit_batch) * self.policy.max_retries + 2
        )
        for _ in range(limit):
            if self.waiting == 0:
                break
            results.append(self.drain(now, block=True))
        return results
