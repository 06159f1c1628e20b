"""Fully on-device scanned simulator.

``SoASimulator`` already keeps the fleet state device-resident, but its event
loop is python: every event costs one dispatch (plus a host sync at sample
points), and at 10^5 hosts the host<->device ping-pong — not the decision
math — dominates end-to-end throughput.  This module folds the *entire*
event stream into one jitted ``lax.scan``:

* ``EventTrace`` — a struct-of-arrays trace encoding (the same trick as
  ``SoAFleetState.inst_cost_kind``): one i32 ``kind`` column plus payload
  columns (time / size / duration / priority / cost kind / period / zone /
  instance-id), so a whole simulation is a handful of device arrays.
* ``trace_from_workload`` — encoder replaying the exact rng draw order of
  ``SoASimulator`` (``_draw_request`` / ``_draw_lifetime``), so a trace is a
  faithful pre-materialization of the python simulator's event heap.
* ``simulate_scan(trace, policy, state)`` — the arrival / departure /
  failure / storm / checkpoint stream as ONE ``lax.scan`` over a
  ``_step_core``-compatible carry, ``lax.switch``-dispatching on the event
  kind, syncing to host ``SimMetrics`` only at configurable sample points.
* ``simulate_ensemble`` — ``vmap`` of the scan over a stacked-trace (seed)
  axis and optional stacked weigher-multiplier / admission-knob axes: one
  dispatch evaluates hundreds of fleet trajectories (the Monte-Carlo
  substrate for policy sweeps).

Streaming admission (``policy.queue_capacity > 0``) runs INSIDE the scan:
the ``AdmissionQueueState`` arrays ride the carry, arrivals ``queue_push``
instead of dispatching directly, and drains (``queue_select`` with aging →
batched ``_step_core`` → ``queue_pop``, storm degradation included) fire
behind predicate-gated ``lax.cond`` on the same triggers the python front
end uses — SLO deadline crossed (before the event), batch filled by an
arrival, capacity freed by a departure/failure/heal/storm (after it) —
with a ``drain_all``-mirroring ``fori_loop`` epilogue at the last
timestamp.  ``knobs`` traces ``(aging_rate, slo_target_s,
storm_threshold)`` so an admission-policy sweep shares one compiled
program (``storm_threshold=inf`` disables degradation numerically).

Parity contract (pinned by ``tests/test_scan_sim.py``): on integer-time /
integer-resource traces the scanned simulator is **bit-exact** against
``SoASimulator.run_trace`` — final fleet-state arrays, per-arrival
placement/rejection sequences, every ``SimMetrics`` counter, and (in
streaming mode) every admission counter, the final queue arrays, and the
per-placement sim-time wait distribution.  f32 sums
of integers below 2^24 are exact regardless of association, so the fused
device reductions here equal the python loop's sequential adds bitwise;
decisions run the same ``_step_core`` program on both sides, so even
non-integer billing costs (``revenue``) cannot diverge the placements.

Storm semantics are deterministic by construction (no rng inside the scan):
a ``zone_storm`` event kills the ``n`` lowest ``(host, slot)`` flat-indexed
live preemptible slots of the zone, ``n = min(max(1, round_f32(count *
frac)), count)`` — mirrored exactly by ``SoASimulator._trace_storm``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .admission import (
    PAD_RES,
    AdmissionQueueState,
    queue_init,
    queue_pop,
    queue_push,
    queue_select,
)
from .jax_scheduler import (
    SoAFleetState,
    _scan_live_rows,
    _step_core,
    apply_departure,
    apply_host_failure,
    apply_termination,
    ensure_policy,
    set_schedulable,
)
from .policy import COST_KINDS, SchedulerPolicy
from .screen_math import churn_stats
from .simulator import SimMetrics, WorkloadSpec

# -- event kinds --------------------------------------------------------------
ARRIVAL = 0
DEPARTURE = 1
FAIL_HOST = 2
HEAL_HOST = 3
CHECKPOINT = 4
ZONE_STORM = 5
PAD = 6

KIND_NAMES: Tuple[str, ...] = (
    "arrival", "departure", "fail_host", "heal_host", "checkpoint",
    "zone_storm", "pad",
)
KIND_IDS: Dict[str, int] = {name: i for i, name in enumerate(KIND_NAMES)}

#: float payload columns checked for NaN at construction (column, per-row)
_FLOAT_COLS = ("time", "duration", "period", "price", "frac")


@dataclasses.dataclass
class TraceEvent:
    """One decoded trace row (``EventTrace.events`` / ``from_events``)."""

    kind: str
    time: float
    res: Optional[Tuple[float, ...]] = None   # arrival size vector
    preemptible: bool = False
    duration: float = -1.0                    # arrival lifetime (s)
    priority: int = -1
    cost_kind: int = -1                       # COST_KIND_IDS id, -1 = default
    period: float = -1.0
    price: float = 1.0
    domain: int = -1
    zone: int = -1                            # zone_storm target
    frac: float = 0.0                         # zone_storm kill fraction
    inst_id: int = -1                         # departure/checkpoint: arrival row
    host: int = -1                            # fail/heal target host index


@dataclasses.dataclass(frozen=True)
class EventTrace:
    """Struct-of-arrays event trace: ``kind`` i32 + payload columns.

    Rows are time-ordered (non-decreasing).  Non-applicable payloads hold
    sentinel defaults (-1 / 0 / 1.0) so every column is dense and the whole
    trace ships to the device as one pytree of arrays.
    """

    kind: np.ndarray          # (E,)   i32  event kind (KIND_NAMES index)
    time: np.ndarray          # (E,)   f32  event time (s)
    res: np.ndarray           # (E,D)  f32  arrival size vector
    preemptible: np.ndarray   # (E,)   bool arrival preemptible flag
    duration: np.ndarray      # (E,)   f32  arrival lifetime (-1 = n/a)
    priority: np.ndarray      # (E,)   i32  arrival priority (-1 = none)
    cost_kind: np.ndarray     # (E,)   i32  COST_KIND_IDS id (-1 = default)
    period: np.ndarray        # (E,)   f32  billing period (-1 = default)
    price: np.ndarray         # (E,)   f32  price rate
    domain: np.ndarray        # (E,)   i32  anti-affinity domain id (-1 = none)
    zone: np.ndarray          # (E,)   i32  storm target zone (-1 = n/a)
    frac: np.ndarray          # (E,)   f32  storm kill fraction
    inst_id: np.ndarray       # (E,)   i32  departure/checkpoint target =
                              #             ARRIVAL ROW INDEX (-1 = n/a)
    host: np.ndarray          # (E,)   i32  fail/heal target host (-1 = n/a)

    def __post_init__(self):
        coerce = {
            "kind": np.int32, "time": np.float32, "res": np.float32,
            "preemptible": np.bool_, "duration": np.float32,
            "priority": np.int32, "cost_kind": np.int32,
            "period": np.float32, "price": np.float32, "domain": np.int32,
            "zone": np.int32, "frac": np.float32, "inst_id": np.int32,
            "host": np.int32,
        }
        for name, dt in coerce.items():
            object.__setattr__(
                self, name, np.ascontiguousarray(getattr(self, name), dt)
            )
        e = self.kind.shape[0]
        for name in coerce:
            col = getattr(self, name)
            want = 2 if name == "res" else 1
            if col.ndim != want or col.shape[0] != e:
                raise ValueError(
                    f"trace column {name!r} has shape {col.shape}, expected "
                    f"{e} rows ({want}-d)"
                )
        bad = np.nonzero((self.kind < 0) | (self.kind > PAD))[0]
        if bad.size:
            i = int(bad[0])
            raise ValueError(
                f"unknown event kind {int(self.kind[i])} at row {i} "
                f"(valid: 0..{PAD} = {KIND_NAMES})"
            )
        if not np.all(np.isfinite(self.time)):
            i = int(np.nonzero(~np.isfinite(self.time))[0][0])
            raise ValueError(f"non-finite time at row {i}")
        if e and float(self.time[0]) < 0.0:
            raise ValueError("negative time at row 0")
        drop = np.nonzero(np.diff(self.time) < 0)[0]
        if drop.size:
            i = int(drop[0])
            raise ValueError(
                f"unsorted times: time[{i + 1}]={float(self.time[i + 1])!r} < "
                f"time[{i}]={float(self.time[i])!r}"
            )
        for name in _FLOAT_COLS[1:] + ("res",):
            col = getattr(self, name)
            nan = np.nonzero(np.isnan(col).reshape(e, -1).any(axis=1))[0]
            if nan.size:
                raise ValueError(
                    f"NaN payload in column {name!r} at row {int(nan[0])}"
                )
        bad = np.nonzero(
            (self.cost_kind < -1) | (self.cost_kind >= len(COST_KINDS))
        )[0]
        if bad.size:
            i = int(bad[0])
            raise ValueError(
                f"unknown cost kind id {int(self.cost_kind[i])} at row {i}"
            )
        arr = self.kind == ARRIVAL
        if np.any(arr & ~np.all(np.isfinite(self.res), axis=1)):
            i = int(np.nonzero(arr & ~np.all(np.isfinite(self.res), axis=1))[0][0])
            raise ValueError(f"non-finite arrival size at row {i}")
        if np.any(arr & (self.res < 0).any(axis=1)):
            i = int(np.nonzero(arr & (self.res < 0).any(axis=1))[0][0])
            raise ValueError(f"negative arrival size at row {i}")
        for k, what in ((DEPARTURE, "departure"), (CHECKPOINT, "checkpoint")):
            rows = np.nonzero(self.kind == k)[0]
            for i in rows:
                tgt = int(self.inst_id[i])
                if not 0 <= tgt < e or int(self.kind[tgt]) != ARRIVAL:
                    raise ValueError(
                        f"{what} at row {int(i)} targets inst_id={tgt}, "
                        f"which is not an arrival row"
                    )
                if float(self.time[tgt]) > float(self.time[i]):
                    raise ValueError(
                        f"{what} at row {int(i)} precedes its arrival "
                        f"(row {tgt})"
                    )
        for k, what in ((FAIL_HOST, "fail_host"), (HEAL_HOST, "heal_host")):
            rows = np.nonzero((self.kind == k) & (self.host < 0))[0]
            if rows.size:
                raise ValueError(
                    f"{what} at row {int(rows[0])} has no host index"
                )
        rows = np.nonzero(self.kind == ZONE_STORM)[0]
        for i in rows:
            if int(self.zone[i]) < 0:
                raise ValueError(f"zone_storm at row {int(i)} has no zone")
            f = float(self.frac[i])
            if not 0.0 < f <= 1.0:
                raise ValueError(
                    f"zone_storm at row {int(i)} has kill fraction {f!r} "
                    f"outside (0, 1]"
                )

    # -- views ----------------------------------------------------------------
    @property
    def n_events(self) -> int:
        return int(self.kind.shape[0])

    @property
    def n_dims(self) -> int:
        return int(self.res.shape[1])

    def events(self) -> List[TraceEvent]:
        """Decode to a python event list (inverse of ``from_events``)."""
        out = []
        for i in range(self.n_events):
            k = int(self.kind[i])
            out.append(TraceEvent(
                kind=KIND_NAMES[k],
                time=float(self.time[i]),
                res=tuple(float(v) for v in self.res[i]) if k == ARRIVAL else None,
                preemptible=bool(self.preemptible[i]),
                duration=float(self.duration[i]),
                priority=int(self.priority[i]),
                cost_kind=int(self.cost_kind[i]),
                period=float(self.period[i]),
                price=float(self.price[i]),
                domain=int(self.domain[i]),
                zone=int(self.zone[i]),
                frac=float(self.frac[i]),
                inst_id=int(self.inst_id[i]),
                host=int(self.host[i]),
            ))
        return out

    @classmethod
    def from_events(cls, events: Sequence[TraceEvent], n_dims: int) -> "EventTrace":
        """Encode a python event list (inverse of ``events``)."""
        e = len(events)
        cols = dict(
            kind=np.zeros(e, np.int32), time=np.zeros(e, np.float32),
            res=np.zeros((e, n_dims), np.float32),
            preemptible=np.zeros(e, bool),
            duration=np.full(e, -1.0, np.float32),
            priority=np.full(e, -1, np.int32),
            cost_kind=np.full(e, -1, np.int32),
            period=np.full(e, -1.0, np.float32),
            price=np.ones(e, np.float32),
            domain=np.full(e, -1, np.int32),
            zone=np.full(e, -1, np.int32),
            frac=np.zeros(e, np.float32),
            inst_id=np.full(e, -1, np.int32),
            host=np.full(e, -1, np.int32),
        )
        for i, ev in enumerate(events):
            if ev.kind not in KIND_IDS:
                raise ValueError(f"unknown event kind {ev.kind!r} at row {i}")
            cols["kind"][i] = KIND_IDS[ev.kind]
            cols["time"][i] = ev.time
            if ev.res is not None:
                cols["res"][i] = np.asarray(ev.res, np.float32)
            cols["preemptible"][i] = ev.preemptible
            cols["duration"][i] = ev.duration
            cols["priority"][i] = ev.priority
            cols["cost_kind"][i] = ev.cost_kind
            cols["period"][i] = ev.period
            cols["price"][i] = ev.price
            cols["domain"][i] = ev.domain
            cols["zone"][i] = ev.zone
            cols["frac"][i] = ev.frac
            cols["inst_id"][i] = ev.inst_id
            cols["host"][i] = ev.host
        return cls(**cols)

    def padded(self, to: int) -> "EventTrace":
        """Right-pad with PAD rows at the trace's final time (no-ops on both
        engines) so unequal-length traces can stack on an ensemble axis."""
        e = self.n_events
        if to < e:
            raise ValueError(f"cannot pad {e} events down to {to}")
        if to == e:
            return self
        tail = to - e
        t_last = float(self.time[-1]) if e else 0.0
        base = EventTrace.from_events(
            [TraceEvent(kind="pad", time=t_last)], self.n_dims
        )
        cols = {
            f.name: np.concatenate(
                [getattr(self, f.name),
                 np.repeat(getattr(base, f.name), tail, axis=0)]
            )
            for f in dataclasses.fields(self)
        }
        return EventTrace(**cols)


def stack_traces(traces: Sequence[EventTrace]) -> Dict[str, np.ndarray]:
    """Stack traces on a leading ensemble axis, right-padding with PAD rows."""
    if not traces:
        raise ValueError("stack_traces needs at least one trace")
    d = traces[0].n_dims
    if any(t.n_dims != d for t in traces):
        raise ValueError("traces disagree on resource dimensionality")
    emax = max(t.n_events for t in traces)
    padded = [t.padded(emax) for t in traces]
    return {
        f.name: np.stack([getattr(t, f.name) for t in padded])
        for f in dataclasses.fields(EventTrace)
    }


# -- workload encoder ---------------------------------------------------------
def trace_from_workload(
    workload: WorkloadSpec,
    duration_s: float,
    seed: int = 0,
    *,
    integer_times: bool = True,
    storms: Sequence[Tuple[float, int, float]] = (),
    failures: Sequence[Tuple[float, int, Optional[float]]] = (),
    checkpoint_every: int = 0,
    cost_kinds: Sequence[int] = (),
    priorities: Sequence[int] = (),
) -> EventTrace:
    """Pre-materialize a ``SoASimulator`` workload as an ``EventTrace``.

    Replays the simulator's exact rng draw order (initial inter-arrival
    exponential; per arrival: flavor choice, preemptible uniform, <=64
    truncated lifetime exponentials, next inter-arrival), then lowers the
    event heap into time-sorted rows:

    * arrivals carry size/preemptible/duration (+ optional round-robin
      ``cost_kinds`` / ``priorities`` assignment for mixed-billing traces);
    * each placed lifetime emits a ``departure`` row whose ``inst_id`` is
      the ARRIVAL ROW INDEX (resolved to a live instance at run time);
    * ``storms`` = (time, zone_id, kill_frac), ``failures`` = (time,
      host_idx, heal_after_s|None) inject fault rows;
    * ``checkpoint_every=k`` adds a mid-life checkpoint row for every k-th
      preemptible arrival.

    ``integer_times=True`` floors every event time and rounds lifetimes to
    whole seconds — the regime in which scanned-vs-python parity is bitwise
    (f32 integer sums are exact under any association).
    """
    if not workload.flavors:
        raise ValueError("trace_from_workload needs workload.flavors")
    rng = np.random.default_rng(seed)
    w = workload
    names = [f[0] for f in w.flavors]
    d = len(w.flavors[0][1].vec)

    def draw_lifetime() -> float:
        for _ in range(64):
            x = rng.exponential(w.lifetime_mean_s)
            if w.lifetime_min_s <= x <= w.lifetime_max_s:
                return x
        return float(np.clip(x, w.lifetime_min_s, w.lifetime_max_s))

    def q(t: float) -> float:
        return float(np.floor(t)) if integer_times else float(t)

    events: List[Tuple[float, int, TraceEvent]] = []
    seq = 0

    def emit(t: float, ev: TraceEvent) -> None:
        nonlocal seq
        ev.time = t
        events.append((t, seq, ev))
        seq += 1

    arrivals: List[TraceEvent] = []
    t = rng.exponential(1.0 / w.arrival_rate_per_s)
    n_arr = 0
    while t <= duration_s:
        now = q(t)
        idx = rng.choice(len(names), p=w.flavor_probs)
        _, res = w.flavors[idx]
        preempt = bool(rng.random() < w.preemptible_fraction)
        life = draw_lifetime()
        if integer_times:
            life = max(1.0, float(np.round(life)))
        ev = TraceEvent(
            kind="arrival", time=now,
            res=tuple(float(v) for v in res.vec32),
            preemptible=preempt, duration=life,
            cost_kind=(cost_kinds[n_arr % len(cost_kinds)] if cost_kinds else -1),
            priority=(priorities[n_arr % len(priorities)] if priorities else -1),
        )
        if ev.cost_kind == COST_KINDS.index("period"):
            ev.period = max(60.0, float(np.round(life / 4.0)))
        elif ev.cost_kind == COST_KINDS.index("revenue"):
            ev.period = 3600.0
        emit(now, ev)
        arrivals.append(ev)
        dep_t = now + life
        if dep_t <= duration_s:
            emit(dep_t, TraceEvent(kind="departure", time=dep_t))
            events[-1][2].inst_id = len(arrivals) - 1  # patched to row below
        if preempt and checkpoint_every and n_arr % checkpoint_every == 0:
            ck_t = q(now + life / 2.0)
            if ck_t <= min(dep_t, duration_s):
                emit(ck_t, TraceEvent(kind="checkpoint", time=ck_t))
                events[-1][2].inst_id = len(arrivals) - 1
        n_arr += 1
        t += rng.exponential(1.0 / w.arrival_rate_per_s)
    for at, zone, frac in storms:
        emit(q(at), TraceEvent(kind="zone_storm", time=q(at), zone=int(zone),
                               frac=float(frac)))
    for at, host, heal_after in failures:
        emit(q(at), TraceEvent(kind="fail_host", time=q(at), host=int(host)))
        if heal_after is not None:
            ht = q(at + heal_after)
            emit(ht, TraceEvent(kind="heal_host", time=ht, host=int(host)))
    events.sort(key=lambda x: (x[0], x[1]))
    # inst_id currently indexes `arrivals`; remap to sorted row indices
    row_of = {id(ev): i for i, (_, _, ev) in enumerate(events)}
    ordered = [ev for _, _, ev in events]
    for ev in ordered:
        if ev.kind in ("departure", "checkpoint") and ev.inst_id >= 0:
            ev.inst_id = row_of[id(arrivals[ev.inst_id])]
    return EventTrace.from_events(ordered, d)


# -- the scanned event loop ---------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class _ScanCarry:
    state: SoAFleetState
    slot_owner: jax.Array    # (N, K) i32 arrival row occupying the slot (-1)
    ev_host: jax.Array       # (E+1,) i32 placement host per arrival row
    ev_slot: jax.Array       # (E+1,) i32 placement slot (-1 = normal)
    ev_live: jax.Array       # (E+1,) bool instance still running
    normal_res: jax.Array    # (N, D) f32 live NORMAL resources per host
    counters: jax.Array      # (7,) i32 [placed_n, placed_p, failed_n,
                             #           failed_p, preemptions, storms,
                             #           storm_kills]
    next_sample: jax.Array   # () f32
    n_samp: jax.Array        # () i32
    samp_t: jax.Array        # (E+1,) f32 sample times
    samp_f: jax.Array        # (E+1,) f32 free_f[:, 0] sums at samples
    samp_n: jax.Array        # (E+1,) f32 free_n[:, 0] sums at samples
    # -- streaming admission plane (policy.queue_capacity > 0; else None) ----
    qstate: Optional[AdmissionQueueState] = None  # the in-carry wait queue
    q_src: Optional[jax.Array] = None     # (Q,) i32 trace row per queue slot
    ev_ok: Optional[jax.Array] = None     # (E+1,) bool arrival row placed
    ev_kill: Optional[jax.Array] = None   # (E+1,) i32 victims of the placement
    ev_pre: Optional[jax.Array] = None    # (E+1,) bool EFFECTIVE (post-
                                          # degradation) preemptible flag
    ev_wait: Optional[jax.Array] = None   # (E+1,) f32 sim-time queue wait
                                          # at placement (-1 = never placed)
    adm: Optional[jax.Array] = None       # (9,) i32 admission counters
    next_deadline: Optional[jax.Array] = None  # () f32 earliest enq + SLO

    def tree_flatten(self):
        return tuple(getattr(self, f.name) for f in dataclasses.fields(self)), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


_C_PLACED_N, _C_PLACED_P, _C_FAILED_N, _C_FAILED_P = 0, 1, 2, 3
_C_PREEMPT, _C_STORMS, _C_STORM_KILLS = 4, 5, 6

_A_ARRIVALS, _A_ADMITTED, _A_REJ_OVER, _A_REJ_RETRY = 0, 1, 2, 3
_A_DRAINS, _A_RETRIES, _A_DEGRADED, _A_ATTEMPTS, _A_FALLBACKS = 4, 5, 6, 7, 8
_ADM_NAMES = (
    "arrivals", "admitted", "rejected_overflow", "rejected_retry", "drains",
    "retries", "degraded", "attempts", "fallbacks",
)

_COL_ORDER = tuple(f.name for f in dataclasses.fields(EventTrace))


def _scan_impl(state, cols, normal_res0, sample_every, mult, knobs, policy,
               with_mult, with_knobs):
    (kind, time, res, pre, dur, prio, ck, per, price, dom, zone, frac,
     inst_id, host) = cols
    e_total = kind.shape[0]
    n, k = state.inst_valid.shape
    d = state.free_f.shape[1]
    slot_ids = jnp.arange(k)
    mult_val = tuple(mult[i] for i in range(len(policy.all_multipliers))) \
        if with_mult else None
    streaming = policy.queue_capacity > 0
    # Admission knobs: static policy floats by default; TRACED scalars on the
    # ensemble knob axis.  Traced neutral values (aging 0, storm inf) are
    # numerically inert, so the always-computed traced program is outcome-
    # bit-exact against the statically-gated one.
    if with_knobs:
        aging, slo, storm_thr = knobs[0], knobs[1], knobs[2]
    else:
        aging = policy.aging_rate
        slo = jnp.float32(policy.slo_target_s)
        storm_thr = (
            None if policy.storm_threshold is None
            else jnp.float32(policy.storm_threshold)
        )

    def record_sample(c, t):
        do = t >= c.next_sample
        si = c.n_samp
        f0 = jnp.sum(c.state.free_f[:, 0])
        n0 = jnp.sum(c.state.free_n[:, 0])
        return dataclasses.replace(
            c,
            samp_t=c.samp_t.at[si].set(jnp.where(do, t, c.samp_t[si])),
            samp_f=c.samp_f.at[si].set(jnp.where(do, f0, c.samp_f[si])),
            samp_n=c.samp_n.at[si].set(jnp.where(do, n0, c.samp_n[si])),
            n_samp=si + do.astype(jnp.int32),
            next_sample=jnp.where(do, t + sample_every, c.next_sample),
        )

    no_y = (jnp.int32(-1), jnp.int32(-1), jnp.asarray(False), jnp.int32(0))

    @jax.named_scope("arrival")
    def ev_arrival(c, ev):
        e, t, r, p, pr, ckk, pd, pc, dm, zn, fr, tg, hs = ev
        if streaming:
            # Route through the in-carry wait queue instead of deciding
            # inline; the decision happens at the next drain boundary.
            klass = jnp.where(
                pr >= 0, pr,
                jnp.where(p, jnp.int32(policy.n_classes - 1), jnp.int32(0)),
            )
            with jax.named_scope("queue_push"):
                q, slot, okp = queue_push(
                    c.qstate, r, p, dm, ckk, pd, jnp.int32(-1), klass, t, pc,
                )
            adm = c.adm.at[_A_ARRIVALS].add(1)
            adm = adm.at[_A_REJ_OVER].add((~okp).astype(jnp.int32))
            counters = c.counters
            counters = counters.at[_C_FAILED_N].add(
                (~okp & ~p).astype(jnp.int32)
            )
            counters = counters.at[_C_FAILED_P].add(
                (~okp & p).astype(jnp.int32)
            )
            # queue_push's slot is garbage when the push was rejected — keep
            # the old source row in that case.
            q_src = c.q_src.at[slot].set(
                jnp.where(okp, e.astype(jnp.int32), c.q_src[slot])
            )
            nd = jnp.where(
                okp, jnp.minimum(c.next_deadline, t + slo), c.next_deadline
            )
            c = dataclasses.replace(
                c, qstate=q, q_src=q_src, adm=adm, counters=counters,
                next_deadline=nd,
            )
            return c, no_y
        st, (h, s, ok, kill, _fb, _mg) = _step_core(
            c.state, r, p, dm, t, pc, ckk, pd, policy,
            req_exclude=jnp.int32(-1), mult_val=mult_val,
        )
        n_kill = jnp.sum(kill.astype(jnp.int32))
        owner_row = c.slot_owner[h]
        dead = jnp.where(kill & (owner_row >= 0), owner_row, e_total)
        ev_live = c.ev_live.at[dead].set(False)
        placed_pre = ok & p
        owner_row = jnp.where(kill, -1, owner_row)
        owner_row = jnp.where(
            (slot_ids == s) & placed_pre, e.astype(jnp.int32), owner_row
        )
        r0 = jnp.where(ok & ~p, r, jnp.zeros_like(r))
        counters = c.counters
        counters = counters.at[_C_PLACED_N].add((ok & ~p).astype(jnp.int32))
        counters = counters.at[_C_PLACED_P].add(placed_pre.astype(jnp.int32))
        counters = counters.at[_C_FAILED_N].add((~ok & ~p).astype(jnp.int32))
        counters = counters.at[_C_FAILED_P].add((~ok & p).astype(jnp.int32))
        counters = counters.at[_C_PREEMPT].add(n_kill)
        c = dataclasses.replace(
            c, state=st,
            slot_owner=c.slot_owner.at[h].set(owner_row),
            ev_live=ev_live.at[e].set(ok),
            ev_host=c.ev_host.at[e].set(jnp.where(ok, h, -1)),
            ev_slot=c.ev_slot.at[e].set(jnp.where(placed_pre, s, -1)),
            normal_res=c.normal_res.at[h].add(r0),
            counters=counters,
        )
        y = (jnp.where(ok, h, -1).astype(jnp.int32),
             jnp.where(placed_pre, s, -1).astype(jnp.int32), ok, n_kill)
        return c, y

    @jax.named_scope("departure")
    def ev_departure(c, ev):
        e, t, r, p, pr, ckk, pd, pc, dm, zn, fr, tg, hs = ev
        tgc = jnp.clip(tg, 0, e_total)
        live = c.ev_live[tgc]
        h = jnp.maximum(c.ev_host[tgc], 0)
        s = jnp.clip(c.ev_slot[tgc], 0, k - 1)
        # Streaming: storm degradation may have demoted the placement to
        # NORMAL capacity — the trace's preemptible column lies; the carry's
        # EFFECTIVE flag is the truth.
        is_pre = c.ev_pre[tgc] if streaming else pre[tgc]
        mask = (slot_ids == s) & live & is_pre
        st = apply_termination(c.state, h, mask, now=t, involuntary=False)
        radd = res[tgc] * (live & ~is_pre).astype(jnp.float32)
        st = apply_departure(st, h, radd)
        owner_row = jnp.where(mask, -1, c.slot_owner[h])
        c = dataclasses.replace(
            c, state=st,
            slot_owner=c.slot_owner.at[h].set(owner_row),
            ev_live=c.ev_live.at[tgc].set(False),
            normal_res=c.normal_res.at[h].add(-radd),
        )
        return c, no_y

    @jax.named_scope("fail_host")
    def ev_fail(c, ev):
        e, t, r, p, pr, ckk, pd, pc, dm, zn, fr, tg, hs = ev
        h = jnp.clip(hs, 0, n - 1)
        st = apply_host_failure(c.state, h, c.normal_res[h], now=t)
        on_h = c.ev_live & (c.ev_host == h)
        c = dataclasses.replace(
            c, state=st,
            slot_owner=c.slot_owner.at[h].set(jnp.full((k,), -1, jnp.int32)),
            ev_live=c.ev_live & ~on_h,
            normal_res=c.normal_res.at[h].set(jnp.zeros((d,), jnp.float32)),
        )
        return c, no_y

    @jax.named_scope("heal_host")
    def ev_heal(c, ev):
        e, t, r, p, pr, ckk, pd, pc, dm, zn, fr, tg, hs = ev
        h = jnp.clip(hs, 0, n - 1)
        return dataclasses.replace(
            c, state=set_schedulable(c.state, h, jnp.asarray(True))
        ), no_y

    @jax.named_scope("checkpoint")
    def ev_checkpoint(c, ev):
        e, t, r, p, pr, ckk, pd, pc, dm, zn, fr, tg, hs = ev
        tgc = jnp.clip(tg, 0, e_total)
        # fleet.checkpoint no-ops on normal instances, so a demoted
        # (effectively normal) streaming placement must not take one.
        live = c.ev_live[tgc] & (c.ev_pre[tgc] if streaming else pre[tgc])
        h = jnp.maximum(c.ev_host[tgc], 0)
        s = jnp.clip(c.ev_slot[tgc], 0, k - 1)
        row = jnp.where((slot_ids == s) & live, t, c.state.inst_ckpt[h])
        st = dataclasses.replace(
            c.state, inst_ckpt=c.state.inst_ckpt.at[h].set(row)
        )
        return dataclasses.replace(c, state=st), no_y

    @jax.named_scope("zone_storm")
    def ev_storm(c, ev):
        e, t, r, p, pr, ckk, pd, pc, dm, zn, fr, tg, hs = ev
        st = c.state
        live = st.inst_valid & (st.host_zone[:, None] == zn)
        flat = live.reshape(-1)
        cnt = jnp.sum(flat.astype(jnp.int32))
        want = jnp.maximum(
            1, jnp.round(cnt.astype(jnp.float32) * fr).astype(jnp.int32)
        )
        n_kill = jnp.where(cnt > 0, jnp.minimum(want, cnt), 0)
        kill_flat = flat & (jnp.cumsum(flat.astype(jnp.int32)) <= n_kill)
        kill = kill_flat.reshape(n, k)
        freed = jnp.sum(jnp.where(kill[:, :, None], st.inst_res, 0.0), axis=1)
        up = jnp.sum(jnp.where(kill, t - st.inst_start, 0.0))
        zc = jnp.clip(zn, 0, st.zone_term.shape[0] - 1)
        st = dataclasses.replace(
            st,
            free_f=st.free_f + freed,
            inst_valid=st.inst_valid & ~kill,
            zone_term=st.zone_term.at[zc].add(n_kill.astype(jnp.float32)),
            zone_up=st.zone_up.at[zc].add(up),
        )
        owner_flat = c.slot_owner.reshape(-1)
        dead = jnp.where(kill_flat & (owner_flat >= 0), owner_flat, e_total)
        counters = c.counters.at[_C_STORMS].add(1)
        counters = counters.at[_C_STORM_KILLS].add(n_kill)
        c = dataclasses.replace(
            c, state=st,
            slot_owner=jnp.where(kill, -1, c.slot_owner),
            ev_live=c.ev_live.at[dead].set(False),
            counters=counters,
        )
        return c, no_y

    def ev_pad(c, ev):
        return c, no_y

    branches = (ev_arrival, ev_departure, ev_fail, ev_heal, ev_checkpoint,
                ev_storm, ev_pad)

    @jax.named_scope("drain")
    def drain(c, now):
        """One in-carry admission drain: select → ``_step_core`` loop → pop.

        The pure-transition mirror of ``admission._drain_entry`` (minus the
        push scan — arrivals were already pushed at their event rows), with
        the host mirror's bookkeeping (``AdmissionFrontEnd.flush``) folded
        into the carry arrays instead of python lists.  Like it, the loop
        runs the taken rows only (``_scan_live_rows``); a skipped row
        leaves the carry as it was and reads ``ok`` and ``fell_back``
        False, which the bookkeeping masks by ``take`` in any case.
        """
        q = c.qstate
        with jax.named_scope("queue_select"):
            idx, take = queue_select(
                q, policy.admit_batch, now=now, aging_rate=aging,
                n_classes=policy.n_classes,
            )
            b = idx.shape[0]
            b_res = jnp.where(take[:, None], q.res[idx], PAD_RES)
            b_pre = jnp.where(take, q.preemptible[idx], False)
            b_dom = jnp.where(take, q.domain[idx], -1)
            b_kind = jnp.where(take, q.cost_kind[idx], -1)
            b_period = jnp.where(take, q.period[idx], -1.0)
            b_price = jnp.where(take, q.price[idx], 1.0)
            b_now = jnp.full((b,), now, jnp.float32)
            src = jnp.where(take, c.q_src[idx], e_total).astype(jnp.int32)

        orig_pre = b_pre
        if storm_thr is None:
            degraded = jnp.zeros_like(b_pre)
        else:
            # storm_thr == +inf (the traced-knob "off" value) makes the
            # predicate constant-False: exactly the no-degradation program.
            churn = churn_stats(c.state.zone_term, c.state.zone_up)[-1]
            storm = churn > storm_thr
            degraded = b_pre & storm
            b_pre = b_pre & ~storm

        def attempt(cc, xs):
            src_e, r, p, dm, t_, pc_, kd_, pd_ = xs
            st, (h, s, ok, kill, fb, _mg) = _step_core(
                cc.state, r, p, dm, t_, pc_, kd_, pd_, policy,
                req_exclude=None, mult_val=mult_val,
            )
            n_kill = jnp.sum(kill.astype(jnp.int32))
            owner_row = cc.slot_owner[h]
            dead = jnp.where(kill & (owner_row >= 0), owner_row, e_total)
            ev_live = cc.ev_live.at[dead].set(False)
            placed_pre = ok & p
            owner_row = jnp.where(kill, -1, owner_row)
            owner_row = jnp.where(
                (slot_ids == s) & placed_pre, src_e, owner_row
            )
            r0 = jnp.where(ok & ~p, r, jnp.zeros_like(r))
            counters = cc.counters
            counters = counters.at[_C_PLACED_N].add(
                (ok & ~p).astype(jnp.int32)
            )
            counters = counters.at[_C_PLACED_P].add(
                placed_pre.astype(jnp.int32)
            )
            counters = counters.at[_C_PREEMPT].add(n_kill)
            cc = dataclasses.replace(
                cc, state=st,
                slot_owner=cc.slot_owner.at[h].set(owner_row),
                ev_live=ev_live.at[src_e].set(ev_live[src_e] | ok),
                ev_host=cc.ev_host.at[src_e].set(
                    jnp.where(ok, h, cc.ev_host[src_e])
                ),
                ev_slot=cc.ev_slot.at[src_e].set(
                    jnp.where(placed_pre, s, cc.ev_slot[src_e])
                ),
                ev_ok=cc.ev_ok.at[src_e].set(cc.ev_ok[src_e] | ok),
                ev_kill=cc.ev_kill.at[src_e].add(n_kill),
                ev_pre=cc.ev_pre.at[src_e].set(
                    jnp.where(ok, p, cc.ev_pre[src_e])
                ),
                normal_res=cc.normal_res.at[h].add(r0),
                counters=counters,
            )
            return cc, (ok, fb)

        with jax.named_scope("decide"):
            c, (ok_b, fb_b) = _scan_live_rows(
                attempt, c,
                (src, b_res, b_pre, b_dom, b_now, b_price, b_kind, b_period),
                take, (jnp.bool_(False), jnp.bool_(False)),
            )
        with jax.named_scope("queue_pop"):
            placed = ok_b & take
            wait = jnp.where(placed, now - q.enq_t[idx], 0.0)
            ev_wait = c.ev_wait.at[src].set(
                jnp.where(placed, wait, c.ev_wait[src])
            )
            q2, dropped = queue_pop(q, idx, take, placed, policy.max_retries)
        # Rejections (retries exhausted) book as failures under the ORIGINAL
        # preemptible flag — the queue stores it; demotion is per-attempt.
        counters = c.counters
        counters = counters.at[_C_FAILED_N].add(
            jnp.sum((dropped & ~orig_pre).astype(jnp.int32))
        )
        counters = counters.at[_C_FAILED_P].add(
            jnp.sum((dropped & orig_pre).astype(jnp.int32))
        )
        adm = c.adm
        adm = adm.at[_A_ADMITTED].add(jnp.sum(placed.astype(jnp.int32)))
        adm = adm.at[_A_REJ_RETRY].add(jnp.sum(dropped.astype(jnp.int32)))
        adm = adm.at[_A_RETRIES].add(
            jnp.sum((take & ~placed & ~dropped).astype(jnp.int32))
        )
        adm = adm.at[_A_DEGRADED].add(jnp.sum(degraded.astype(jnp.int32)))
        adm = adm.at[_A_DRAINS].add(1)
        adm = adm.at[_A_ATTEMPTS].add(jnp.sum(take.astype(jnp.int32)))
        adm = adm.at[_A_FALLBACKS].add(
            jnp.sum((fb_b & take).astype(jnp.int32))
        )
        nd = jnp.min(
            jnp.where(q2.valid, q2.enq_t, jnp.float32(jnp.inf))
        ) + slo
        return dataclasses.replace(
            c, qstate=q2, ev_wait=ev_wait, adm=adm, counters=counters,
            next_deadline=nd,
        )

    def step(c, xs):
        kd = xs[0]
        ev = xs[1:]
        t = ev[1]
        c = record_sample(c, t)
        if not streaming:
            return lax.switch(jnp.clip(kd, 0, PAD), branches, c, ev)
        # SLO pre-drain: the incoming event's timestamp crossing the oldest
        # waiting entry's deadline forces (at most) one drain first.
        c = lax.cond(
            t >= c.next_deadline, lambda cc: drain(cc, t), lambda cc: cc, c
        )
        c, y = lax.switch(jnp.clip(kd, 0, PAD), branches, c, ev)
        # Post-event drain triggers: a full admit batch after an arrival, or
        # freed capacity (departure/failure/heal/storm) while entries wait.
        depth = c.qstate.depth
        freeing = (
            (kd == DEPARTURE) | (kd == FAIL_HOST) | (kd == HEAL_HOST)
            | (kd == ZONE_STORM)
        )
        fire = ((kd == ARRIVAL) & (depth >= policy.admit_batch)) \
            | (freeing & (depth > 0))
        c = lax.cond(fire, lambda cc: drain(cc, t), lambda cc: cc, c)
        return c, y

    s1 = e_total + 1
    carry0 = _ScanCarry(
        state=state,
        slot_owner=jnp.full((n, k), -1, jnp.int32),
        ev_host=jnp.full((s1,), -1, jnp.int32),
        ev_slot=jnp.full((s1,), -1, jnp.int32),
        ev_live=jnp.zeros((s1,), bool),
        normal_res=normal_res0,
        counters=jnp.zeros((7,), jnp.int32),
        next_sample=jnp.float32(0.0),
        n_samp=jnp.int32(0),
        samp_t=jnp.zeros((s1,), jnp.float32),
        samp_f=jnp.zeros((s1,), jnp.float32),
        samp_n=jnp.zeros((s1,), jnp.float32),
    )
    if streaming:
        carry0 = dataclasses.replace(
            carry0,
            qstate=queue_init(policy.queue_capacity, d),
            q_src=jnp.full((policy.queue_capacity,), e_total, jnp.int32),
            ev_ok=jnp.zeros((s1,), bool),
            ev_kill=jnp.zeros((s1,), jnp.int32),
            ev_pre=jnp.zeros((s1,), bool),
            ev_wait=jnp.full((s1,), -1.0, jnp.float32),
            adm=jnp.zeros((len(_ADM_NAMES),), jnp.int32),
            next_deadline=jnp.float32(jnp.inf),
        )
    xs = (kind, jnp.arange(e_total, dtype=jnp.int32), time, res, pre, prio,
          ck, per, price, dom, zone, frac, inst_id, host)
    carry, ys = lax.scan(step, carry0, xs)
    t_last = time[e_total - 1] if e_total else jnp.float32(0.0)
    stream = None
    if streaming:
        # End-of-run epilogue (``AdmissionFrontEnd.drain_all``): every
        # still-waiting entry gets its retries.  Each failing entry burns one
        # retry per drain, so ceil(Q/B) * max_retries + 2 rounds suffice.
        limit = (
            -(-policy.queue_capacity // policy.admit_batch)
            * policy.max_retries + 2
        )

        def _epilogue(_, cc):
            return lax.cond(
                cc.qstate.depth > 0, lambda c2: drain(c2, t_last),
                lambda c2: c2, cc,
            )

        with jax.named_scope("epilogue"):
            carry = lax.fori_loop(0, limit, _epilogue, carry)
        # Per-arrival outcomes resolve at drain boundaries, not event rows —
        # read them off the final carry instead of the scan's ys.
        ys = (carry.ev_host[:e_total], carry.ev_slot[:e_total],
              carry.ev_ok[:e_total], carry.ev_kill[:e_total])
        stream = (carry.qstate, carry.adm, carry.ev_wait[:e_total],
                  carry.qstate.depth)
    # final host sample, mirroring the python loop's closing _sample()
    si = carry.n_samp
    return (
        carry.state,
        ys,
        carry.counters,
        (
            carry.samp_t.at[si].set(t_last),
            carry.samp_f.at[si].set(jnp.sum(carry.state.free_f[:, 0])),
            carry.samp_n.at[si].set(jnp.sum(carry.state.free_n[:, 0])),
            si + 1,
        ),
        stream,
    )


@functools.lru_cache(maxsize=64)
def _scan_fn(policy: SchedulerPolicy, with_mult: bool, with_knobs: bool):
    def run(state, cols, normal_res0, sample_every, mult, knobs):
        return _scan_impl(
            state, cols, normal_res0, sample_every, mult, knobs, policy,
            with_mult, with_knobs,
        )
    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _ensemble_fn(policy: SchedulerPolicy, with_mult: bool, with_knobs: bool):
    def run(state, cols, normal_res0, sample_every, mult, knobs):
        return _scan_impl(
            state, cols, normal_res0, sample_every, mult, knobs, policy,
            with_mult, with_knobs,
        )
    return jax.jit(
        jax.vmap(run, in_axes=(
            None, 0, None, None,
            0 if with_mult else None,
            0 if with_knobs else None,
        ))
    )


@dataclasses.dataclass
class ScanResult:
    """Host-side view of one scanned trajectory.

    Streaming-mode runs (``policy.queue_capacity > 0``) additionally carry
    the final queue arrays, the admission counter dict (the keys of
    ``AdmissionStats.summary()``'s integer counters), and the per-arrival
    sim-time queue wait (``-1`` = never placed); they are ``None`` on
    direct-mode runs.
    """

    state: SoAFleetState
    host: np.ndarray       # (E,) i32 winning host per arrival row (-1)
    slot: np.ndarray       # (E,) i32 winning slot (-1 = normal / rejected)
    ok: np.ndarray         # (E,) bool placement succeeded
    n_kill: np.ndarray     # (E,) i32 victims evacuated by the placement
    counters: Dict[str, int]
    sample_t: np.ndarray        # (S,) f32 sample times
    sample_free0: np.ndarray    # (S,) f32 sum(free_f[:, 0]) at each sample
    sample_free0_normal: np.ndarray  # (S,) f32 sum(free_n[:, 0])
    #: final wait-queue arrays (streaming mode only; numpy-materialized)
    queue: Optional[AdmissionQueueState] = None
    #: admission counters: arrivals / admitted / rejected_overflow /
    #: rejected_retry / drains / retries / degraded / attempts / fallbacks
    #: / queue_depth
    admission: Optional[Dict[str, int]] = None
    #: (E,) f32 sim-time enqueue→absorb wait per arrival row (-1 = never
    #: placed: rejected, or a non-arrival row)
    wait_s: Optional[np.ndarray] = None

    def wait_percentiles(self) -> Dict[str, float]:
        """Sim-time queue-wait p50/p99 over the placed arrivals — the same
        reader as ``AdmissionStats.wait_percentiles`` over the python front
        end, bit-identical on a shared trace (the waits are the same f32
        differences computed by the same drain program)."""
        if self.wait_s is None:
            return {"wait_p50_s": 0.0, "wait_p99_s": 0.0}
        w = np.asarray(self.wait_s)
        w = w[w >= 0.0]
        if not w.size:
            return {"wait_p50_s": 0.0, "wait_p99_s": 0.0}
        return {
            "wait_p50_s": float(np.percentile(w, 50)),
            "wait_p99_s": float(np.percentile(w, 99)),
        }

    def sim_metrics(self, cap0_total: float) -> SimMetrics:
        """Materialize ``SimMetrics`` exactly as the python loop would: the
        device ships raw f32 free-capacity sums; the utilization ratio is
        computed host-side in float64, bitwise-matching
        ``SoAFleet.utilization`` (which also sums on device and divides on
        host).  ``sched_latency_s`` is wall-clock-dependent and stays empty."""
        m = SimMetrics()
        for t, f, fn in zip(
            self.sample_t, self.sample_free0, self.sample_free0_normal
        ):
            m.t.append(float(t))
            if not cap0_total:
                m.utilization.append(0.0)
                m.utilization_normal.append(0.0)
            else:
                m.utilization.append((cap0_total - float(f)) / cap0_total)
                m.utilization_normal.append((cap0_total - float(fn)) / cap0_total)
        for name, val in self.counters.items():
            setattr(m, name, val)
        return m


_COUNTER_NAMES = (
    "placed_normal", "placed_preemptible", "failures_normal",
    "failures_preemptible", "preemptions", "storms", "storm_kills",
)


def _check_policy(policy: SchedulerPolicy, where: str) -> None:
    # Everything else — including the streaming admission plane
    # (queue_capacity > 0) — runs inside the scan; see
    # docs/scan_sim.md#which-planes-scan for the full support matrix.
    if policy.relocation_on:
        raise NotImplementedError(
            f"{where}: the relocation plane runs host-side passes between "
            f"events (victim identity bookkeeping) and is not folded into "
            f"the scanned loop; see docs/scan_sim.md#which-planes-scan"
        )
    if policy.mesh is not None:
        raise NotImplementedError(
            f"{where}: sharded fleet state is not supported under the scan; "
            f"see docs/scan_sim.md#which-planes-scan"
        )
    if policy.adaptive_shortlist:
        raise NotImplementedError(
            f"{where}: adaptive_shortlist mutates the policy between batches "
            f"(host-side controller) and cannot run inside one scan; see "
            f"docs/scan_sim.md#which-planes-scan"
        )


def _check_trace(trace: EventTrace, state: SoAFleetState,
                 policy: SchedulerPolicy) -> None:
    n = state.inst_valid.shape[0]
    n_zones = state.zone_term.shape[0]
    if trace.n_dims != state.free_f.shape[1]:
        raise ValueError(
            f"trace has {trace.n_dims} resource dims, fleet has "
            f"{state.free_f.shape[1]}"
        )
    fail = np.isin(trace.kind, (FAIL_HOST, HEAL_HOST))
    if np.any(fail & (trace.host >= n)):
        raise ValueError(f"fail/heal host index out of range (fleet has {n})")
    if np.any((trace.kind == ZONE_STORM) & (trace.zone >= n_zones)):
        raise ValueError(
            f"zone_storm zone index out of range (fleet has {n_zones} zones)"
        )
    table_ids = {-1} | {COST_KINDS.index(kname) for kname in policy.kind_table}
    arr = trace.kind == ARRIVAL
    bad = np.unique(trace.cost_kind[arr & ~np.isin(trace.cost_kind,
                                                   sorted(table_ids))])
    if bad.size:
        raise ValueError(
            f"trace bills by cost kind ids {bad.tolist()}, not in the "
            f"policy's kind table {policy.kind_table}"
        )
    if policy.queue_capacity:
        if np.any(arr & (trace.priority >= policy.n_classes)):
            i = int(np.nonzero(arr & (trace.priority >= policy.n_classes))[0][0])
            raise ValueError(
                f"arrival at row {i} has priority {int(trace.priority[i])} "
                f"outside the policy's {policy.n_classes} classes"
            )
        headroom = 1 << (32 - int(policy.n_classes).bit_length())
        if trace.n_events >= headroom:
            raise ValueError(
                f"trace has {trace.n_events} rows but the packed "
                f"queue_select key holds only {headroom} seq tickets at "
                f"n_classes={policy.n_classes}"
            )


def _check_mult(mult: np.ndarray, policy: SchedulerPolicy) -> np.ndarray:
    gates = policy.all_multipliers
    mult = np.asarray(mult, np.float32)
    if mult.shape[-1] != len(gates):
        raise ValueError(
            f"multiplier rows must have {len(gates)} entries "
            f"(weigher + churn), got shape {mult.shape}"
        )
    flat = mult.reshape(-1, len(gates))
    for i, g in enumerate(gates):
        if g == 0.0 and np.any(flat[:, i] != 0.0):
            raise ValueError(
                f"multiplier column {i} must be 0 everywhere: the policy's "
                f"static multiplier gates that term off at compile time"
            )
        if i == 1 and g != 0.0 and np.any(np.sign(flat[:, i]) != np.sign(g)):
            raise ValueError(
                "termination multipliers on the ensemble axis must keep the "
                "static multiplier's sign (the screening bound side is "
                "compiled from it)"
            )
    if np.any(~np.isfinite(mult)):
        raise ValueError("non-finite multiplier on the ensemble axis")
    return mult


def _check_knobs(knobs, policy: SchedulerPolicy) -> np.ndarray:
    """Validate a ``(..., 3)`` array of traced admission-knob rows:
    ``(aging_rate, slo_target_s, storm_threshold)``.  ``storm_threshold =
    np.inf`` disables degradation for that lane (the predicate ``churn >
    inf`` is constant-False)."""
    if not policy.queue_capacity:
        raise ValueError(
            "admission knobs need a streaming policy (queue_capacity > 0)"
        )
    knobs = np.asarray(knobs, np.float32)
    if knobs.shape[-1] != 3:
        raise ValueError(
            f"knob rows must be (aging_rate, slo_target_s, storm_threshold), "
            f"got shape {knobs.shape}"
        )
    flat = knobs.reshape(-1, 3)
    if np.any(~np.isfinite(flat[:, 0])) or np.any(flat[:, 0] < 0):
        raise ValueError("aging_rate knob must be finite and >= 0")
    if np.any(~np.isfinite(flat[:, 1])) or np.any(flat[:, 1] <= 0):
        raise ValueError("slo_target_s knob must be finite and > 0")
    if np.any(np.isnan(flat[:, 2])) or np.any(flat[:, 2] <= 0):
        raise ValueError(
            "storm_threshold knob must be > 0 (np.inf = degradation off)"
        )
    return knobs


def _device_cols(cols: Dict[str, np.ndarray]):
    return tuple(jnp.asarray(cols[name]) for name in _COL_ORDER)


def _lane_result(state, ys, counters, samples, stream=None) -> ScanResult:
    h, s, ok, n_kill = (np.asarray(y) for y in ys)
    samp_t, samp_f, samp_n, n_samp = samples
    n_samp = int(n_samp)
    queue = admission = wait_s = None
    if stream is not None:
        qstate, adm, ev_wait, depth = stream
        queue = jax.tree_util.tree_map(np.asarray, qstate)
        adm = np.asarray(adm)
        admission = {name: int(adm[i]) for i, name in enumerate(_ADM_NAMES)}
        admission["queue_depth"] = int(depth)
        wait_s = np.asarray(ev_wait)
    return ScanResult(
        state=state,
        host=h, slot=s, ok=ok, n_kill=n_kill,
        counters={
            name: int(np.asarray(counters)[i])
            for i, name in enumerate(_COUNTER_NAMES)
        },
        sample_t=np.asarray(samp_t)[:n_samp],
        sample_free0=np.asarray(samp_f)[:n_samp],
        sample_free0_normal=np.asarray(samp_n)[:n_samp],
        queue=queue, admission=admission, wait_s=wait_s,
    )


def simulate_scan(
    trace: EventTrace,
    policy: Optional[SchedulerPolicy],
    state: SoAFleetState,
    *,
    normal_res: Optional[np.ndarray] = None,
    sample_every_s: float = 300.0,
    mult: Optional[np.ndarray] = None,
    knobs: Optional[np.ndarray] = None,
) -> ScanResult:
    """Run ``trace`` against ``state`` as ONE jitted ``lax.scan`` dispatch.

    ``normal_res`` seeds the per-host live-normal-resource tracker (needed
    only when the starting state already hosts normal instances that a
    ``fail_host`` row may evacuate); defaults to zeros.  ``mult`` optionally
    substitutes TRACED weigher/churn multiplier values (same zero pattern
    and m_term sign as the policy's static ones — see ``simulate_ensemble``).

    With ``policy.queue_capacity > 0`` the run is in **streaming admission
    mode**: arrivals queue through the in-carry ``AdmissionQueueState`` and
    drains fire inside the scan (see docs/scan_sim.md), bit-exact against
    the python front end (``SoASimulator.run_trace`` streaming replay).
    ``knobs`` then optionally substitutes one TRACED ``(aging_rate,
    slo_target_s, storm_threshold)`` row for the policy's static values
    (``np.inf`` threshold = degradation off).

    Returns a ``ScanResult``: the final fleet state, the per-arrival
    placement/rejection sequence, metric counters, and the sample-point
    series (``.sim_metrics(cap0_total)`` materializes ``SimMetrics``).
    """
    policy = ensure_policy(policy, "simulate_scan")
    _check_policy(policy, "simulate_scan")
    _check_trace(trace, state, policy)
    n, d = state.free_f.shape
    if normal_res is None:
        normal_res = np.zeros((n, d), np.float32)
    with_mult = mult is not None
    if with_mult:
        mult = _check_mult(mult, policy)
        if mult.ndim != 1:
            raise ValueError("simulate_scan takes one multiplier row; use "
                             "simulate_ensemble for a stacked axis")
    else:
        mult = np.zeros((len(policy.all_multipliers),), np.float32)
    with_knobs = knobs is not None
    if with_knobs:
        knobs = _check_knobs(knobs, policy)
        if knobs.ndim != 1:
            raise ValueError("simulate_scan takes one knob row; use "
                             "simulate_ensemble for a stacked axis")
    else:
        knobs = np.zeros((3,), np.float32)
    cols = {name: getattr(trace, name) for name in _COL_ORDER}
    out_state, ys, counters, samples, stream = _scan_fn(
        policy, with_mult, with_knobs
    )(
        state, _device_cols(cols), jnp.asarray(normal_res, jnp.float32),
        jnp.float32(sample_every_s), jnp.asarray(mult), jnp.asarray(knobs),
    )
    return _lane_result(out_state, ys, counters, samples, stream)


def simulate_ensemble(
    traces: Sequence[EventTrace],
    policy: Optional[SchedulerPolicy],
    state: SoAFleetState,
    *,
    mults: Optional[np.ndarray] = None,
    knobs: Optional[np.ndarray] = None,
    normal_res: Optional[np.ndarray] = None,
    sample_every_s: float = 300.0,
) -> List[ScanResult]:
    """Monte-Carlo harness: ``vmap`` the scanned loop over a stacked-trace
    (seed) axis and, optionally, stacked weigher-multiplier and
    admission-knob axes.

    ``traces`` are right-padded with no-op PAD rows and stacked; ``mults``
    is a ``(P, len(policy.all_multipliers))`` array of TRACED multiplier
    values zipped lane-for-lane with the traces; ``knobs`` (streaming
    policies only) is a ``(P, 3)`` array of TRACED ``(aging_rate,
    slo_target_s, storm_threshold)`` rows — a whole admission-policy sweep
    in one dispatch.  Any axis of length 1 broadcasts against the others.
    Each lane is bitwise identical to the corresponding single
    ``simulate_scan`` dispatch on integer-cost traces (pinned by
    tests/test_scan_sim.py).

    Multiplier rows must preserve the static policy's zero pattern and
    m_term sign: zeros gate terms out at COMPILE time (``consts_of`` folds),
    and the screening bound side is compiled from ``sign(m_term)`` — traced
    values may change magnitudes, never structure.  Knob rows have no such
    structural constraint (``storm_threshold=np.inf`` turns degradation off
    numerically, not structurally).
    """
    policy = ensure_policy(policy, "simulate_ensemble")
    _check_policy(policy, "simulate_ensemble")
    if policy.use_pallas or policy.fused_screen:
        raise NotImplementedError(
            "simulate_ensemble: the pallas/fused stage-1 kernels do not "
            "support the ensemble batch axis; use the jnp path"
        )
    if policy.fused_screen is None:
        policy = dataclasses.replace(policy, fused_screen=False)
    traces = list(traces)
    if not traces:
        raise ValueError("simulate_ensemble needs at least one trace")
    with_mult = mults is not None
    if with_mult:
        mults = _check_mult(mults, policy)
        if mults.ndim != 2:
            raise ValueError("mults must be (P, n_multipliers)")
    with_knobs = knobs is not None
    if with_knobs:
        knobs = _check_knobs(knobs, policy)
        if knobs.ndim != 2:
            raise ValueError(
                "knobs must be (P, 3) rows of (aging_rate, slo_target_s, "
                "storm_threshold)"
            )
    n_lanes = max(
        len(traces),
        mults.shape[0] if with_mult else 1,
        knobs.shape[0] if with_knobs else 1,
    )
    if len(traces) == 1 and n_lanes > 1:
        traces = traces * n_lanes
    if with_mult and mults.shape[0] == 1 and n_lanes > 1:
        mults = np.repeat(mults, n_lanes, axis=0)
    if with_knobs and knobs.shape[0] == 1 and n_lanes > 1:
        knobs = np.repeat(knobs, n_lanes, axis=0)
    if with_mult and mults.shape[0] != len(traces):
        raise ValueError(
            f"{len(traces)} traces vs {mults.shape[0]} multiplier rows"
        )
    if with_knobs and knobs.shape[0] != len(traces):
        raise ValueError(
            f"{len(traces)} traces vs {knobs.shape[0]} knob rows"
        )
    if not with_mult:
        mults = np.zeros(
            (len(traces), len(policy.all_multipliers)), np.float32
        )
    if not with_knobs:
        knobs = np.zeros((len(traces), 3), np.float32)
    for t in traces:
        _check_trace(t, state, policy)
    n, d = state.free_f.shape
    if normal_res is None:
        normal_res = np.zeros((n, d), np.float32)
    stacked = stack_traces(traces)
    out_state, ys, counters, samples, stream = _ensemble_fn(
        policy, with_mult, with_knobs
    )(
        state, _device_cols(stacked), jnp.asarray(normal_res, jnp.float32),
        jnp.float32(sample_every_s), jnp.asarray(mults), jnp.asarray(knobs),
    )
    lanes = []
    n_lanes = len(traces)
    state_np = jax.tree_util.tree_map(np.asarray, out_state)
    stream_np = (
        None if stream is None
        else jax.tree_util.tree_map(np.asarray, stream)
    )
    for i in range(n_lanes):
        e = traces[i].n_events
        lane_state = jax.tree_util.tree_map(lambda a: a[i], state_np)
        lane_stream = None
        if stream_np is not None:
            qst, adm, ev_wait, depth = stream_np
            lane_stream = (
                jax.tree_util.tree_map(lambda a: a[i], qst),
                adm[i], ev_wait[i, :e], depth[i],
            )
        lanes.append(_lane_result(
            lane_state,
            tuple(np.asarray(y)[i, :e] for y in ys),
            np.asarray(counters)[i],
            tuple(np.asarray(s)[i] for s in samples),
            lane_stream,
        ))
    return lanes
