"""Plain reference of the scheduler's semantics, independent of the program.

It holds the fleet as numpy arrays in float64 (the zone uptime accumulator
in float32, which is the precision the configuration states for it), and
implements, from the paper (arXiv:1812.10668 Alg. 2, 5, 6) and the
admission queue's documented contract:

* the decision.  A normal request filters hosts on their normal view
  (preemptible instances seen through) and needs some subset of the
  host's preemptible instances whose release makes it fit; a preemptible
  request needs room in the full view and a free slot.  Hosts are weighed
  by overcommit (a host where the request fits as is wins) and then by
  the termination cost of the best subset; ties go to the lowest host
  index.  On the winner, the victims are the cheapest feasible subset,
  ties by fewer instances and then by the lowest slot bitmask.  The cost
  of an instance is its run time modulo the billing period.
* the transitions: placement (a preemptible instance takes the first free
  slot), termination of victims, departures, and the per-zone churn
  accumulators (victim count and lost uptime; voluntary departures add
  uptime only).
* the admission queue: a bounded queue, priority class 0 for normal and
  1 for preemptible requests, first in first out within a class; a drain
  takes the first ``admit_batch`` entries in that order and decides them
  one after the other at the drain's time; a failed entry stays and is
  retried, and is refused once it has failed ``max_retries`` times; an
  arrival finding the queue full is refused at once.

``decide(..., precision="bfloat16")`` reads every number of the state and
the request rounded to bfloat16 and rounds each result of its arithmetic:
the reference in the precision below the configuration's float32, the
control that a correct check must refuse.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import ml_dtypes
import numpy as np

EPS = 1e-6           # resource comparison slack (whole-number sizes)
TIE_COST = 1e-3      # subset costs this close count as tied


def _masks(p: int) -> np.ndarray:
    """(2^p, p) 0/1 rows: every subset of p slots, row m = bitmask m."""
    m = np.arange(1 << p)
    return ((m[:, None] >> np.arange(p)[None, :]) & 1).astype(np.float64)


_MASKS = {p: _masks(p) for p in range(0, 17)}


def _exact(x):
    return x


def _bf16(x):
    """Round to bfloat16 (and back to float64 for the arithmetic)."""
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(np.float64)


@dataclasses.dataclass
class Decision:
    ok: bool
    host: int = -1
    slot: int = -1               # preemptible placements only
    victims: Tuple[int, ...] = ()  # slot indices on ``host``


class RefFleet:
    """The fleet's state as the reference keeps it.

    Every quantity a decision reads per host (its rounded view of the
    state, its fit tests, its best-subset cost at one time) is a function
    of that host's row alone.  Such a quantity is kept between decisions
    and recomputed for the hosts that a transition changed since
    (``_changed``), so a decision over a million hosts reads a few cached
    arrays instead of recomputing them."""

    def __init__(self, fleet, period: float):
        n, k = fleet.n, fleet.k_slots
        d = fleet.cap.shape[0]
        self.k = k
        self.period = float(period)
        self.free_f = np.tile(fleet.cap, (n, 1))
        self.free_n = np.tile(fleet.cap, (n, 1))
        self.valid = np.zeros((n, k), bool)
        self.res = np.zeros((n, k, d))
        self.start = np.zeros((n, k))
        self.slot_id: List[List[Optional[str]]] = [[None] * k for _ in range(n)]
        self.where: Dict[str, Tuple[int, int, bool, np.ndarray]] = {}
        self.zone_term = np.float32(0.0)
        self.zone_up = np.float32(0.0)
        #: hosts changed by each transition, in order; a cache keeps how
        #: far into it its rows are current
        self._changed: List[int] = []
        self._cache: Dict[tuple, Tuple[np.ndarray, int]] = {}
        # every instance counts on the full view, normal ones on both;
        # preemptible instances fill slots in id order on each host
        host = np.asarray(fleet.host, np.int64)
        pre = np.asarray(fleet.pre, bool)
        np.subtract.at(self.free_f, host, fleet.res)
        np.subtract.at(self.free_n, host[~pre], fleet.res[~pre])
        order = np.argsort(np.asarray(fleet.ids), kind="stable")
        order = order[pre[order]]
        order = order[np.argsort(host[order], kind="stable")]
        hp = host[order]
        group = np.r_[0, np.flatnonzero(hp[1:] != hp[:-1]) + 1]
        slot = np.arange(hp.size) - np.repeat(group, np.diff(np.r_[group, hp.size]))
        self.valid[hp, slot] = True
        self.res[hp, slot] = fleet.res[order]
        self.start[hp, slot] = fleet.start[order]
        slots = np.full(host.size, -1, np.int64)
        slots[order] = slot
        ids = fleet.ids
        for i, h, s in zip(order.tolist(), hp.tolist(), slot.tolist()):
            self.slot_id[h][s] = ids[i]
        self.where = dict(zip(ids, zip(host.tolist(), slots.tolist(),
                                       pre.tolist(), fleet.res)))

    def _set_slot(self, h, s, iid, res, start):
        self.valid[h, s] = True
        self.res[h, s] = res
        self.start[h, s] = start
        self.slot_id[h][s] = iid
        self.where[iid] = (h, s, True, res)

    def _rows(self, key: tuple, fn) -> np.ndarray:
        """``fn(hosts)`` for every host, computed once and kept under
        ``key``; the rows of hosts changed since are recomputed."""
        got = self._cache.get(key)
        if got is None:
            val = fn(np.arange(self.free_f.shape[0]))
        else:
            val, seen = got
            if seen < len(self._changed):
                hs = np.unique(np.asarray(self._changed[seen:], np.int64))
                val[hs] = fn(hs)
        self._cache[key] = (val, len(self._changed))
        return val

    # -- the decision ----------------------------------------------------------
    def _view(self, precision: str):
        """The state's numbers as the decision reads them: as kept, or every
        one of them rounded to bfloat16 (the control)."""
        if precision == "float32":
            return self.free_f, self.free_n, self.res, self.start, _exact
        return (self._rows(("bf16", "free_f"), lambda hs: _bf16(self.free_f[hs])),
                self._rows(("bf16", "free_n"), lambda hs: _bf16(self.free_n[hs])),
                self._rows(("bf16", "res"), lambda hs: _bf16(self.res[hs])),
                self._rows(("bf16", "start"), lambda hs: _bf16(self.start[hs])),
                _bf16)

    def best_subset(self, h: int, req, now: float, precision: str = "float32"):
        """Cheapest feasible victim subset on host ``h`` as (cost, slots),
        or None when releasing every preemptible instance is not enough."""
        free_f, _, res, start, rnd = self._view(precision)
        req, now = rnd(np.asarray(req, np.float64)), rnd(now)
        slots = np.flatnonzero(self.valid[h])
        m = _MASKS[len(slots)]
        freed = rnd(m @ res[h, slots])                          # (2^p, D)
        ok = np.all(rnd(free_f[h] + freed) >= req - EPS, axis=1)
        if not ok.any():
            return None
        cost = rnd(m @ rnd(np.mod(rnd(now - start[h, slots]), self.period)))
        cost = np.where(ok, cost, np.inf)
        best = cost.min()
        tied = np.flatnonzero(cost <= best + TIE_COST)
        size = m[tied].sum(axis=1)
        pick = tied[np.flatnonzero(size == size.min())[0]]
        return best, tuple(int(s) for s in slots[m[pick] > 0])

    def decide(self, req: np.ndarray, pre: bool, now: float,
               precision: str = "float32") -> Decision:
        free_f, free_n, res, _, rnd = self._view(precision)
        req = rnd(np.asarray(req, np.float64))
        key = (precision, tuple(req))
        if pre:
            fit = self._rows(("pre",) + key, lambda hs: np.all(
                free_f[hs] >= req - EPS, axis=1) & ~self.valid[hs].all(axis=1))
            h = int(np.argmax(fit))
            if not fit[h]:
                return Decision(False)
            return Decision(True, h, int(np.argmin(self.valid[h])))

        def cand_of(hs):
            pool = rnd(free_f[hs] + rnd((res[hs] * self.valid[hs][..., None]).sum(axis=1)))
            return (np.all(free_n[hs] >= req - EPS, axis=1)
                    & np.all(pool >= req - EPS, axis=1))

        cand = self._rows(("cand",) + key, cand_of)
        direct = self._rows(("direct",) + key, lambda hs: cand[hs] & np.all(
            free_f[hs] >= req - EPS, axis=1))
        h = int(np.argmax(direct))
        if direct[h]:
            return Decision(True, h)
        hs = np.flatnonzero(cand)
        if not hs.size:
            return Decision(False)
        cost = self._all_costs(req, now, precision)[hs]
        i = int(np.argmin(cost))             # first minimum = lowest index
        h = int(hs[i])
        _, victims = self.best_subset(h, req, now, precision)
        return Decision(True, h, -1, victims)

    def _all_costs(self, req: np.ndarray, now: float, precision: str) -> np.ndarray:
        """Best feasible subset cost of every host (inf where none), kept
        between decisions at one time."""
        key = ("cost", now, tuple(req), precision)
        if key not in self._cache:
            for k in [k for k in self._cache if k[0] == "cost" and k[1] != now]:
                del self._cache[k]
        return self._rows(key, lambda hs: self._host_costs(hs, req, now, precision))

    def _host_costs(self, hs: np.ndarray, req: np.ndarray, now: float,
                    precision: str):
        """Best feasible subset cost of every host in ``hs`` (vectorized by
        the number of preemptible instances a host holds)."""
        free_f, _, res, start, rnd = self._view(precision)
        now = rnd(now)
        out = np.full(hs.shape[0], np.inf)
        valid = self.valid[hs]
        p = valid.sum(axis=1)
        # stable: a host's valid slots keep their order
        order = np.argsort(~valid, axis=1, kind="stable")
        costs = rnd(np.mod(rnd(now - start[hs]), self.period))
        for pv in np.unique(p):
            sel = np.flatnonzero(p == pv)
            cols = order[sel, :pv]
            rows = hs[sel]
            m = _MASKS[int(pv)]
            c = rnd(np.take_along_axis(costs[sel], cols, axis=1) @ m.T)  # (G, 2^p)
            ok = np.ones(c.shape, bool)
            for d in range(req.shape[0]):
                r = np.take_along_axis(res[rows, :, d], cols, axis=1) @ m.T
                ok &= rnd(free_f[rows, d][:, None] + rnd(r)) >= req[d] - EPS
            out[sel] = np.where(ok, c, np.inf).min(axis=1)
        return out

    # -- transitions -----------------------------------------------------------
    def apply(self, dec: Decision, req, pre, now, iid: str) -> None:
        if not dec.ok:
            return
        h = dec.host
        self._changed.append(h)
        if dec.victims:
            lost = np.float32(0.0)
            for s in dec.victims:
                lost = np.float32(lost + np.float32(now - self.start[h, s]))
                self.free_f[h] += self.res[h, s]
                del self.where[self.slot_id[h][s]]
                self.slot_id[h][s] = None
                self.valid[h, s] = False
            self.zone_term = np.float32(self.zone_term + np.float32(len(dec.victims)))
            self.zone_up = np.float32(self.zone_up + lost)
        self.free_f[h] -= req
        if pre:
            self._set_slot(h, dec.slot, iid, req, now)
        else:
            self.free_n[h] -= req
            self.where[iid] = (h, -1, False, np.asarray(req, np.float64))

    def depart(self, iid: str, now: float) -> bool:
        """Voluntary departure; False when the instance is gone already."""
        w = self.where.pop(iid, None)
        if w is None:
            return False
        h, s, pre, res = w
        self._changed.append(h)
        self.free_f[h] += res
        if pre:
            self.zone_up = np.float32(self.zone_up + np.float32(now - self.start[h, s]))
            self.valid[h, s] = False
            self.slot_id[h][s] = None
        else:
            self.free_n[h] += res
        return True

    # -- comparison with the program's arrays -----------------------------------
    def arrays(self) -> Dict[str, np.ndarray]:
        """The reference's fleet in the program's array form."""
        f32 = np.float32
        return {"free_f": self.free_f.astype(f32), "free_n": self.free_n.astype(f32),
                "inst_valid": self.valid, "inst_res": self.res.astype(f32),
                "inst_start": self.start.astype(f32),
                "zone_term": np.asarray([self.zone_term]),
                "zone_up": np.asarray([self.zone_up])}

    def state_mismatches(self, st) -> int:
        """Cells of the program's final fleet arrays that differ from the
        reference's (slot contents compared where a slot is live)."""
        f32 = np.float32
        bad = 0
        bad += int(np.sum(np.asarray(st["free_f"]) != self.free_f.astype(f32)))
        bad += int(np.sum(np.asarray(st["free_n"]) != self.free_n.astype(f32)))
        v = np.asarray(st["inst_valid"])
        bad += int(np.sum(v != self.valid))
        both = v & self.valid
        bad += int(np.sum((np.asarray(st["inst_res"]) != self.res.astype(f32))[both]))
        bad += int(np.sum((np.asarray(st["inst_start"]) != self.start.astype(f32))[both]))
        bad += int(np.sum(np.asarray(st["zone_term"]) != self.zone_term))
        bad += int(np.sum(np.asarray(st["zone_up"]) != self.zone_up))
        return bad


@dataclasses.dataclass
class Entry:
    rid: int
    klass: int
    seq: int
    enq_t: float
    tries: int = 0


class RefQueue:
    """The admission queue as the reference keeps it."""

    def __init__(self, capacity: int, batch: int, max_retries: int):
        self.capacity, self.batch, self.max_retries = capacity, batch, max_retries
        self.live: List[Entry] = []
        self.seq = 0

    def push(self, rid: int, pre: bool, t: float) -> bool:
        if len(self.live) >= self.capacity:
            return False
        self.live.append(Entry(rid, 1 if pre else 0, self.seq, t))
        self.seq += 1
        return True

    def select(self) -> List[Entry]:
        return sorted(self.live, key=lambda e: (e.klass, e.seq))[: self.batch]

    def settle(self, entry: Entry, placed: bool) -> bool:
        """Fold one attempt back; True when the entry is refused for good."""
        if placed:
            self.live.remove(entry)
            return False
        entry.tries += 1
        if entry.tries >= self.max_retries:
            self.live.remove(entry)
            return True
        return False

    def oldest(self) -> Optional[float]:
        return min((e.enq_t for e in self.live), default=None)
