"""The batch cell: jobs of requests through the program's direct entry,
``SoAFleet.schedule_batch`` (one ``schedule_many`` scan per job, padded to
a power of two of at least 4 rows).

There is no clock and no queue.  Each job goes to ``schedule_batch`` as
one call, in order, as soon as the previous job's outcomes are absorbed;
its members all ask at the job's time.  A request that no host takes is
refused at once (the direct path's contract: no retry).  Before each job,
placed instances whose lifetime has ended by the job's time depart through
``SoAFleet.depart``.

Every call into the program is logged with what it returned, for the
reference to replay once the window has closed (``check``).
"""
from __future__ import annotations

import contextlib
import heapq
import time
from typing import Dict, Iterable, List

import numpy as np

import fleetgen
from reference import RefFleet
from served import _iid, program_fleet


class Batch:
    """One batch run: the program's fleet and the log of every call into
    it (``("job", k, outcomes)`` and ``("dep", reference id, now,
    returned)``)."""

    def __init__(self, repro, fleet, jobs: fleetgen.Jobs, policy, trace: bool):
        self.repro = repro
        self.jobs = jobs
        self.fleet = program_fleet(repro, fleet, policy)
        self.trace = trace
        types = repro.types
        self._size = {
            tuple(r): types.Resources(types.VM_SPEC, r)
            for r in np.unique(jobs.arr.res, axis=0)
        }
        self.ops: List[tuple] = []
        self.iid: Dict[int, str] = {}
        self.deps: List[tuple] = []            # (departure time, request)
        self.sent = 0                          # jobs sent
        self.lost = 0                          # members with no outcome
        self.warm_placed = 0                   # warm-up requests placed
        self.departed = 0                      # departures sent by jobs
        #: per job sent: (wall time its outcomes were absorbed, decisions,
        #: rows of its scan)
        self.done: List[tuple] = []

    def span(self, name):
        if not self.trace:
            return contextlib.nullcontext()
        return self.repro.TraceAnnotation(name)

    def depart(self, key: str, iid: str, now: float) -> None:
        with self.span("bench.depart"):
            ok = self.fleet.depart(iid, now=now)
        self.ops.append(("dep", key, now, bool(ok)))

    def run_job(self, k: int) -> int:
        """Send job ``k`` (after the departures due by its time) and absorb
        its outcomes; returns its decisions."""
        jobs, arr = self.jobs, self.jobs.arr
        t = float(jobs.t[k])
        while self.deps and self.deps[0][0] <= t:
            td, j = heapq.heappop(self.deps)
            self.depart(f"r{j}", self.iid[j], td)
            self.departed += 1
        lo, hi = int(jobs.start[k]), int(jobs.start[k + 1])
        request = self.repro.types.Request
        with self.span("bench.pack"):
            items = [(request(id=f"r{j}", resources=self._size[tuple(arr.res[j])],
                              preemptible=bool(arr.pre[j])), t, 1.0)
                     for j in range(lo, hi)]
        with self.span("bench.schedule"):
            outs = self.fleet.schedule_batch(items)
        with self.span("bench.absorb"):
            got = []
            for j, out in zip(range(lo, hi), outs):
                if out.ok:
                    slot = int(out.instance.metadata.get("slot", -1))
                    victims = frozenset(_iid(v.id) for v in out.victims)
                    got.append((True, int(out.host[1:]), slot, victims))
                    self.iid[j] = out.instance.id
                    heapq.heappush(self.deps, (t + float(arr.life[j]), j))
                else:
                    got.append((False, -1, -1, frozenset()))
            self.ops.append(("job", k, got))
        self.done.append((time.perf_counter(), len(outs), bucket(hi - lo)))
        self.lost += (hi - lo) - len(outs)
        self.sent += 1
        return len(outs)


def bucket(size: int) -> int:
    """The rows ``schedule_batch`` runs for a job of ``size`` requests: one
    step for a single request, else the scan padded to the next power of
    two, at least 4."""
    return 1 if size == 1 else max(4, 1 << (size - 1).bit_length())


def warm_up(b: Batch, fleet, sizes: Iterable[int]) -> None:
    """Compile the shape of every job size in ``sizes``, on the run's own
    fleet, with requests larger than any node (each is refused and changes
    nothing), and both departure transitions with two prefilled instances,
    one of each kind (logged, so the reference replays them)."""
    types = b.repro.types
    big = types.Resources(types.VM_SPEC, 2.0 * fleet.cap)
    for a in sorted({bucket(s) for s in sizes}):
        outs = b.fleet.schedule_batch(
            [(types.Request(id=f"w{a}-{i}", resources=big), fleetgen.T0, 1.0)
             for i in range(a)])
        b.warm_placed += sum(o.ok for o in outs)
    for pre in (True, False):
        i = int(np.flatnonzero(fleet.pre == pre)[0])
        b.depart(fleet.ids[i], fleet.ids[i], fleetgen.T0)
    b.repro.jax.block_until_ready(b.fleet.state)


def run_window(b: Batch, seconds: float, tick=None):
    """Send job after job until ``seconds`` of wall time have passed; the
    job running at that mark ends the window.  Returns (decisions, wall
    seconds, jobs run out)."""
    w0 = time.perf_counter()
    decided = 0
    while time.perf_counter() - w0 < seconds:
        if tick is not None:
            tick()
        if b.sent >= len(b.jobs):
            return decided, time.perf_counter() - w0, True
        decided += b.run_job(b.sent)
    return decided, time.perf_counter() - w0, False


def _outcome(ref: RefFleet, dec, pre: bool) -> tuple:
    if not dec.ok:
        return (False, -1, -1, frozenset())
    return (True, dec.host, dec.slot if pre else -1,
            frozenset(ref.slot_id[dec.host][v] for v in dec.victims))


def check(b: Batch, state, fleet, config, control: bool = False) -> Dict[str, int]:
    """Replay the logged calls through the reference, job by job at each
    job's time, and count where the program's answers differ.  With
    ``control`` the program's answers are replaced by the reference's in
    bfloat16, decided from the same states (the control the check has to
    refuse)."""
    jobs, arr = b.jobs, b.jobs.arr
    ref = RefFleet(fleet, config["policy"]["period_s"])
    bad = {"decisions": 0, "refusals": 0, "departures": 0}
    for op in b.ops:
        if op[0] == "dep":
            bad["departures"] += int(ref.depart(op[1], op[2]) != op[3])
            continue
        k, got = op[1], op[2]
        t = float(jobs.t[k])
        lo, hi = int(jobs.start[k]), int(jobs.start[k + 1])
        for i, j in enumerate(range(lo, hi)):
            req, pre = arr.res[j], bool(arr.pre[j])
            dec = ref.decide(req, pre, t)
            mine = _outcome(ref, dec, pre)
            if control:
                theirs = _outcome(ref, ref.decide(req, pre, t, "bfloat16"), pre)
            else:
                theirs = got[i] if i < len(got) else None
            bad["decisions"] += int(theirs != mine)
            bad["refusals"] += int(theirs is None or theirs[0] != mine[0])
            ref.apply(dec, req, pre, t, f"r{j}")
    bad["decisions"] += b.warm_placed
    bad["state_cells"] = ref.state_mismatches(state)
    return bad
