"""The served cells: requests go through ``SoAFleet.submit`` and are decided
by ``SoAFleet.drain``, as a tenant's request reaches the scheduler.

* ``open``: the trace is replayed in wall-clock time as an open loop.  Time
  runs ``lapse`` simulated seconds per wall second, so requests are due at
  the traffic file's wall rate.  A drain is dispatched without blocking
  after an arrival fills a batch (``batch_ready``), and when the SLO
  deadline of the oldest waiting request (``next_deadline``) has passed at
  one of the SLO checks the streaming simulator makes (``slo_target_s``
  after each arrival); a request that failed and waits for its retry keeps
  its old deadline, so each check drains while one waits.  Departures call
  ``SoAFleet.depart``.  The harness absorbs a dispatched drain's
  outcomes as soon as nothing is due, and before any call that needs the
  host's mirror to be current.  A request's latency runs from its due time
  to the absorption of its decision.
* ``backlog``: no clock.  Before each drain the harness submits arrivals
  in trace order until the admission plane holds ``queue_capacity``
  requests; simulated time follows the trace, and departures are applied
  once their simulated time has passed.

Every call into the program is logged with what it returned, for the
reference to replay once the window has closed (``check``).
"""
from __future__ import annotations

import contextlib
import heapq
import time
from typing import Dict, List

import numpy as np

import fleetgen
from reference import RefFleet, RefQueue


def program_fleet(repro, fleet: fleetgen.Fleet, policy):
    """The prefilled fleet as the program's ``Host`` list, and the
    ``SoAFleet`` built from it."""
    types = repro.types
    spec = types.VM_SPEC
    cap = types.Resources(spec, fleet.cap)
    hosts = [types.Host(name=f"h{i}", capacity=cap) for i in range(fleet.n)]
    sizes: Dict[tuple, object] = {}
    for j, iid in enumerate(fleet.ids):
        key = tuple(fleet.res[j])
        if key not in sizes:
            sizes[key] = types.Resources(spec, fleet.res[j])
        h = hosts[int(fleet.host[j])]
        h.instances[iid] = types.Instance(
            id=iid, resources=sizes[key], preemptible=bool(fleet.pre[j]),
            host=h.name, start_time=float(fleet.start[j]),
        )
    return repro.SoAFleet(hosts, k_slots=fleet.k_slots, policy=policy)


def _iid(name: str) -> str:
    """Reference key of an instance: a prefilled instance keeps its id, a
    placed request's instance (``i<n>-r<j>``) is keyed by its request."""
    return name.split("-", 1)[1] if name.startswith("i") and "-" in name else name


class Served:
    """One served run: the program's fleet, its front end, and the log of
    every call into it."""

    def __init__(self, repro, fleet, arr: fleetgen.Arrivals, policy, trace: bool):
        self.repro = repro
        self.arr = arr
        self.fleet = program_fleet(repro, fleet, policy)
        self.front = self.fleet.admission
        self.trace = trace
        types = repro.types
        self._size = {
            tuple(r): types.Resources(types.VM_SPEC, r)
            for r in np.unique(arr.res, axis=0)
        }
        n = len(arr)
        self.decided_at = np.full(n, np.nan)   # wall stamp of absorption
        self.overflow = np.zeros(n, bool)      # refused at push: queue full
        self.iid: Dict[int, str] = {}
        self.fresh: List[tuple] = []           # (departure time, j) to schedule
        self.inflight = False
        self.n_decided = 0                     # placements + refusals absorbed
        self.ops: List[tuple] = []             # ("sub", j, t) / ("drain", now)
        #                                        / ("dep", j, now, returned)
        self.drains: List[list] = []           # per drain: attempts in order
        self.refusals: List[set] = []          # per drain: refused requests
        self.longest_wait = 0.0                # longest wait for the device

    def span(self, name):
        if not self.trace:
            return contextlib.nullcontext()
        return self.repro.TraceAnnotation(name)

    # -- calls into the program ------------------------------------------------
    def submit(self, j: int) -> None:
        t = float(self.arr.t[j])
        req = self.repro.types.Request(
            id=f"r{j}", resources=self._size[tuple(self.arr.res[j])],
            preemptible=bool(self.arr.pre[j]),
        )
        with self.span("bench.submit"):
            self.fleet.submit(req, t)
        self.ops.append(("sub", j, t))

    def drain(self, now: float) -> None:
        self.absorb()
        if not self.front.waiting:
            return
        with self.span("bench.drain"):
            self.fleet.drain(now, block=False)
        self.ops.append(("drain", now))
        self.inflight = True

    def absorb(self) -> None:
        """Take every outcome not yet absorbed (the wait for the device comes
        first, in a span of its own)."""
        if self.inflight:
            t = time.perf_counter()
            with self.span("bench.wait"):
                self.repro.jax.block_until_ready(self.fleet.state)
            self.longest_wait = max(self.longest_wait, time.perf_counter() - t)
        with self.span("bench.absorb"):
            results = self.front.take_results()
        self.inflight = False
        if not results:
            return
        stamp = time.perf_counter()
        arr = self.arr
        for dr in results:
            attempts = []
            outs = iter(dr.outcomes)
            for req, ok in dr.attempts:
                j = int(req.id[1:])
                if ok:
                    out = next(outs)
                    slot = int(out.instance.metadata.get("slot", -1))
                    victims = frozenset(_iid(v.id) for v in out.victims)
                    attempts.append((j, True, int(out.host[1:]), slot, victims))
                    self.iid[j] = out.instance.id
                    self.decided_at[j] = stamp
                    self.n_decided += 1
                    dep = max(float(arr.t[j] + arr.life[j]), dr.now)
                    self.fresh.append((dep, j))
                else:
                    attempts.append((j, False, -1, -1, frozenset()))
            tried = {a[0] for a in attempts}
            refused = set()
            for req in dr.rejected:
                j = int(req.id[1:])
                refused.add(j)
                self.decided_at[j] = stamp
                if j in tried:
                    self.n_decided += 1
                else:
                    self.overflow[j] = True
            self.drains.append(attempts)
            self.refusals.append(refused)

    def depart(self, j: int, now: float) -> None:
        """Departure of request ``j``'s instance (absorbs first: the host's
        mirror must know the instance)."""
        self.absorb()
        with self.span("bench.depart"):
            ok = self.fleet.depart(self.iid[j], now=now)
        self.ops.append(("dep", j, now, bool(ok)))

    def conserved(self) -> bool:
        st = self.front.stats
        return st.arrivals == (
            st.admitted + st.rejected + st.queue_depth + self.front.pending
        )


def warm_up(repro, fleet, arr, policy, top: int) -> None:
    """Compile every drain shape a window can reach (arrival buffers of 4 up
    to ``top`` rows) and both departure transitions, on a fleet of its own."""
    warm = Served(repro, fleet, arr, policy, trace=False)
    j, a = 0, 4
    while a <= top:
        for _ in range(a):
            warm.submit(j)
            j += 1
        warm.drain(fleetgen.T0)
        warm.absorb()
        a *= 2
    # one departure of each kind; a placed instance may have been preempted
    # since (its departure then runs nothing), so prefilled ones go
    departed = set()
    for i, iid in enumerate(fleet.ids):
        if bool(fleet.pre[i]) not in departed and warm.fleet.depart(iid, now=fleetgen.T0 + 1.0):
            departed.add(bool(fleet.pre[i]))
    repro.jax.block_until_ready(warm.fleet.state)


def run_open(s: Served, lapse: float, seconds: float, tail_s: float, tick=None):
    """The open loop over ``seconds`` of wall time (and up to ``tail_s``
    past it, until every request due in the window is decided).

    Simulated events are taken in time order; at one time, SLO checks come
    first, then departures, then arrivals.  As in the streaming simulator,
    every arrival at ``t`` brings an SLO check at ``t + slo_target_s``,
    which drains when ``next_deadline()`` has passed.  Returns (due times
    in window seconds, requests due in the window, wall start, lateness of
    each submission in seconds); ``s.longest_step`` keeps the loop's
    longest step (seconds, window second it began, what it ran)."""
    arr = s.arr
    t0 = fleetgen.T0
    slo = float(s.front.policy.slo_target_s)
    due = (arr.t - t0) / lapse
    n_window = int(np.searchsorted(due, seconds, side="left"))
    late = np.full(len(arr), np.nan)
    s.outstanding = []
    next_sample = 0.0
    deps: List[tuple] = []
    i = k = 0            # next arrival, next SLO check (of arrival k)
    w0 = time.perf_counter()
    s.longest_step = (0.0, 0.0, "")
    step_at, step = w0, "start"
    while True:
        now = time.perf_counter()
        if now - step_at > s.longest_step[0]:
            s.longest_step = (now - step_at, step_at - w0, step)
        step_at, step = now, "loop"
        if tick is not None:
            tick()
        if s.fresh:
            for item in s.fresh:
                heapq.heappush(deps, item)
            s.fresh.clear()
        elapsed = time.perf_counter() - w0
        if elapsed >= next_sample:
            due_now = int(np.searchsorted(due, elapsed, side="right"))
            s.outstanding.append(
                (round(elapsed, 3), due_now - s.n_decided - int(s.overflow[:due_now].sum())))
            next_sample += 0.5
        if elapsed >= seconds and (
            elapsed >= seconds + tail_s
            or not np.isnan(s.decided_at[:n_window]).any()
        ):
            break
        t_arr = float(arr.t[i]) if i < len(arr) else np.inf
        t_dep = deps[0][0] if deps else np.inf
        t_slo = float(arr.t[k]) + slo if k < i else np.inf
        t_next = min(t_arr, t_dep, t_slo)
        if t_next == np.inf:
            break
        wait = (t_next - t0) / lapse - elapsed
        if wait > 0:
            if s.inflight:
                step = "absorb"
                s.absorb()
            elif wait > 2e-3:
                step = f"sleep {wait - 1e-3:.3f} s"
                time.sleep(wait - 1e-3)
            continue
        if t_slo <= t_next:
            step = "slo check"
            k += 1
            s.absorb()          # the mirror must be current to read the deadline
            dl = s.front.next_deadline()
            if dl is not None and dl <= t_slo:
                s.drain(t_slo)
            continue
        if t_dep <= t_arr:
            step = "depart"
            t, j = heapq.heappop(deps)
            s.depart(j, t)
            continue
        step = "submit"
        late[i] = elapsed - due[i]
        s.submit(i)
        i += 1
        if s.front.batch_ready():
            s.drain(t_arr)
    s.absorb()
    return due, n_window, w0, late


def run_backlog(s: Served, seconds: float, tick=None):
    """Submit until the admission plane is full, drain, repeat, for
    ``seconds`` of wall time.  Returns (decisions absorbed in the window,
    requests submitted in the window, wall start)."""
    arr = s.arr
    cap = s.front.policy.queue_capacity
    deps: List[tuple] = []
    i = 0
    sim_now = fleetgen.T0
    w0 = time.perf_counter()
    close = w0 + seconds
    decided = 0
    while True:
        if tick is not None:
            tick()
        s.absorb()
        if time.perf_counter() >= close:
            break
        decided = s.n_decided
        for item in s.fresh:
            heapq.heappush(deps, item)
        s.fresh.clear()
        while i < len(arr) and s.front.waiting < cap:
            t = float(arr.t[i])
            while deps and deps[0][0] <= t:
                td, j = heapq.heappop(deps)
                s.depart(j, td)
            s.submit(i)
            sim_now = t
            i += 1
        s.drain(sim_now)
    submitted = sum(1 for op in s.ops if op[0] == "sub")
    return decided, submitted, w0


def check(s: Served, state, fleet, config, control: bool = False) -> Dict[str, int]:
    """Replay the logged calls through the reference and count where the
    program's answers differ.  With ``control`` the program's answers are
    replaced by the reference computed with bfloat16 costs, decided from the
    same states (the control the check has to refuse)."""
    pol = config["policy"]
    arr = s.arr
    ref = RefFleet(fleet, pol["period_s"])
    q = RefQueue(pol["queue_capacity"], pol["admit_batch"], pol["max_retries"])
    bad = {"queue_order": 0, "decisions": 0, "refusals": 0, "departures": 0}
    pending: List[int] = []
    stats = dict(arrivals=0, admitted=0, rejected_overflow=0, rejected_retry=0,
                 drains=0, retries=0)
    d = 0
    for op in s.ops:
        if op[0] == "sub":
            pending.append(op[1])
            stats["arrivals"] += 1
        elif op[0] == "dep":
            if ref.depart(f"r{op[1]}", op[2]) != op[3]:
                bad["departures"] += 1
        else:
            now = op[1]
            refused = set()
            for j in pending:
                if not q.push(j, bool(arr.pre[j]), float(arr.t[j])):
                    refused.add(j)
                    stats["rejected_overflow"] += 1
            pending = []
            stats["drains"] += 1
            got = s.drains[d] if d < len(s.drains) else []
            got_refused = s.refusals[d] if d < len(s.refusals) else set()
            d += 1
            sel = q.select()
            if [e.rid for e in sel] != [a[0] for a in got]:
                bad["queue_order"] += 1
            for k, e in enumerate(sel):
                j = e.rid
                req, pre = arr.res[j], bool(arr.pre[j])
                dec = ref.decide(req, pre, now)
                mine = (dec.ok, dec.host if dec.ok else -1,
                        dec.slot if dec.ok and pre else -1,
                        frozenset(ref.slot_id[dec.host][v] for v in dec.victims))
                if control:
                    alt = ref.decide(req, pre, now, precision="bfloat16")
                    theirs = (alt.ok, alt.host if alt.ok else -1,
                              alt.slot if alt.ok and pre else -1,
                              frozenset(ref.slot_id[alt.host][v] for v in alt.victims))
                elif k < len(got) and got[k][0] == j:
                    theirs = got[k][1:]
                else:
                    theirs = None
                if theirs != mine:
                    bad["decisions"] += 1
                ref.apply(dec, req, pre, now, f"r{j}")
                if dec.ok:
                    stats["admitted"] += 1
                if q.settle(e, dec.ok):
                    refused.add(j)
                    stats["rejected_retry"] += 1
                elif not dec.ok:
                    stats["retries"] += 1
            if refused != got_refused:
                bad["refusals"] += 1
    if d != len(s.drains):
        bad["queue_order"] += abs(len(s.drains) - d)
    st = s.stats
    stats["queue_depth"] = len(q.live)
    prog = dict(arrivals=st.arrivals, admitted=st.admitted,
                rejected_overflow=st.rejected_overflow,
                rejected_retry=st.rejected_retry, drains=st.drains,
                retries=st.retries, queue_depth=st.queue_depth)
    bad["counters"] = sum(int(prog[k] != stats[k]) for k in stats)
    bad["state_cells"] = ref.state_mismatches(state)
    return bad
