"""The what-if cell: one simulated span of the trace replayed by
``simulate_scan`` (the scanned simulator, streaming admission inside the
scan) from the prefilled state, dispatch after dispatch.

The trace holds the generated arrivals and the departures of those whose
lifetime ends inside the span (prefilled instances never depart); it is
padded with no-op rows to ``2 x arrivals`` rows, so every seed compiles to
the same program.  ``check`` replays it through the plain reference with
the scan's drain triggers: before an event whose time has reached the
oldest waiting request's SLO deadline; after an arrival that fills a
batch; after a departure while requests wait; and at the end, until the
queue is empty or every waiting request has used its retries.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

import fleetgen
from reference import RefFleet, RefQueue
from served import program_fleet

ARRIVAL, DEPARTURE, PAD = 0, 1, 6


@dataclasses.dataclass
class Trace:
    kind: np.ndarray       # (E,) int32
    time: np.ndarray       # (E,) float64, whole seconds
    res: np.ndarray        # (E, D) float64 (arrival rows)
    pre: np.ndarray        # (E,) bool (arrival rows)
    life: np.ndarray       # (E,) float64 (arrival rows; -1 elsewhere)
    target: np.ndarray     # (E,) int64: a departure's arrival row (-1 elsewhere)
    n_real: int
    n_arrivals: int


def make_trace(config, sim_rate: float, span: float, seed: int) -> Trace:
    n_arr = max(1, int(round(sim_rate * span)))
    arr = fleetgen.arrivals(config, sim_rate, n_arr, seed)
    end = float(arr.t[-1])
    j = np.arange(n_arr)
    dep_t = arr.t + arr.life
    leaves = dep_t <= end
    # rows sorted by time; at one time departures come before arrivals
    times = np.concatenate([arr.t, dep_t[leaves]])
    order_key = np.concatenate([np.ones(n_arr), np.zeros(int(leaves.sum()))])
    who = np.concatenate([j, j[leaves]])
    order = np.lexsort((who, order_key, times))
    e_real = order.size
    e_pad = 2 * n_arr
    kind = np.full(e_pad, PAD, np.int32)
    time = np.full(e_pad, end)
    d = arr.res.shape[1]
    res = np.zeros((e_pad, d))
    pre = np.zeros(e_pad, bool)
    life = np.full(e_pad, -1.0)
    target = np.full(e_pad, -1, np.int64)
    is_arr = order_key[order] == 1
    kind[:e_real] = np.where(is_arr, ARRIVAL, DEPARTURE)
    time[:e_real] = times[order]
    row_of = np.empty(n_arr, np.int64)
    row_of[who[order][is_arr]] = np.flatnonzero(is_arr)
    src = who[order]
    res[:e_real][is_arr] = arr.res[src[is_arr]]
    pre[:e_real][is_arr] = arr.pre[src[is_arr]]
    life[:e_real][is_arr] = arr.life[src[is_arr]]
    target[:e_real][~is_arr] = row_of[src[~is_arr]]
    return Trace(kind, time, res, pre, life, target, e_real, n_arr)


def event_trace(repro, tr: Trace):
    """The trace in the program's ``EventTrace`` form."""
    e = tr.kind.shape[0]
    return repro.EventTrace(
        kind=tr.kind, time=tr.time.astype(np.float32), res=tr.res.astype(np.float32),
        preemptible=tr.pre, duration=tr.life.astype(np.float32),
        priority=np.full(e, -1, np.int32), cost_kind=np.full(e, -1, np.int32),
        period=np.full(e, -1.0, np.float32), price=np.ones(e, np.float32),
        domain=np.full(e, -1, np.int32), zone=np.full(e, -1, np.int32),
        frac=np.zeros(e, np.float32), inst_id=tr.target.astype(np.int32),
        host=np.full(e, -1, np.int32),
    )


def start_state(repro, fleet, policy):
    return program_fleet(repro, fleet, policy).state


def to_host(res) -> Dict[str, object]:
    st = res.state
    return {
        "host": np.asarray(res.host), "slot": np.asarray(res.slot),
        "ok": np.asarray(res.ok), "n_kill": np.asarray(res.n_kill),
        "counters": dict(res.counters), "admission": dict(res.admission),
        "wait_s": np.asarray(res.wait_s),
        "state": {f: np.asarray(getattr(st, f)) for f in (
            "free_f", "free_n", "inst_valid", "inst_res", "inst_start",
            "zone_term", "zone_up")},
    }


def check(out, tr: Trace, fleet, config) -> Dict[str, int]:
    """Replay the trace through the reference with the scan's triggers and
    count where the program's final answers differ."""
    return compare(out, replay(tr, fleet, config), tr)


def replay(tr: Trace, fleet, config, precision: str = "float32") -> Dict[str, object]:
    """The reference's answers to the whole trace, in the program's form
    (``precision="bfloat16"``: the control)."""
    pol = config["policy"]
    f32 = np.float32
    slo = f32(pol["slo_target_s"])
    ref = RefFleet(fleet, pol["period_s"])
    q = RefQueue(pol["queue_capacity"], pol["admit_batch"], pol["max_retries"])
    e = tr.kind.shape[0]
    host = np.full(e, -1, np.int64)
    slot = np.full(e, -1, np.int64)
    ok = np.zeros(e, bool)
    n_kill = np.zeros(e, np.int64)
    wait = np.full(e, -1.0, np.float32)
    cnt = dict(placed_normal=0, placed_preemptible=0, failures_normal=0,
               failures_preemptible=0, preemptions=0, storms=0, storm_kills=0)
    adm = dict(arrivals=0, admitted=0, rejected_overflow=0, rejected_retry=0,
               drains=0, retries=0, degraded=0)
    deadline = [f32(np.inf)]

    def drain(now):
        adm["drains"] += 1
        for ent in q.select():
            r = ent.rid
            pre = bool(tr.pre[r])
            dec = ref.decide(tr.res[r], pre, now, precision)
            if dec.ok:
                host[r], ok[r] = dec.host, True
                if pre:
                    slot[r] = dec.slot
                n_kill[r] += len(dec.victims)
                cnt["preemptions"] += len(dec.victims)
                cnt["placed_preemptible" if pre else "placed_normal"] += 1
                adm["admitted"] += 1
                wait[r] = f32(f32(now) - f32(ent.enq_t))
            ref.apply(dec, tr.res[r], pre, now, f"r{r}")
            if q.settle(ent, dec.ok):
                adm["rejected_retry"] += 1
                cnt["failures_preemptible" if pre else "failures_normal"] += 1
            elif not dec.ok:
                adm["retries"] += 1
        oldest = q.oldest()
        deadline[0] = f32(np.inf) if oldest is None else f32(f32(oldest) + slo)

    for row in range(e):
        t = float(tr.time[row])
        if f32(t) >= deadline[0]:
            drain(t)
        k = int(tr.kind[row])
        if k == ARRIVAL:
            adm["arrivals"] += 1
            pre = bool(tr.pre[row])
            if q.push(row, pre, t):
                deadline[0] = min(deadline[0], f32(f32(t) + slo))
            else:
                adm["rejected_overflow"] += 1
                cnt["failures_preemptible" if pre else "failures_normal"] += 1
            if len(q.live) >= pol["admit_batch"]:
                drain(t)
        elif k == DEPARTURE:
            ref.depart(f"r{int(tr.target[row])}", t)
            if q.live:
                drain(t)
    t_last = float(tr.time[-1])
    limit = -(-pol["queue_capacity"] // pol["admit_batch"]) * pol["max_retries"] + 2
    for _ in range(limit):
        if q.live:
            drain(t_last)
    adm["queue_depth"] = len(q.live)
    return {"host": host, "slot": slot, "ok": ok, "n_kill": n_kill,
            "wait_s": wait, "counters": cnt, "admission": adm, "ref": ref}


def compare(out, want, tr: Trace) -> Dict[str, int]:
    """Disagreements between the program's answers ``out`` and the
    reference's ``want``."""
    arr_rows = tr.kind == ARRIVAL
    bad = {}
    bad["outcomes"] = int(np.sum(arr_rows & (
        (out["host"] != want["host"]) | (out["slot"] != want["slot"])
        | (out["ok"] != want["ok"]) | (out["n_kill"] != want["n_kill"]))))
    bad["waits"] = int(np.sum(arr_rows & (out["wait_s"] != want["wait_s"])))
    bad["counters"] = sum(
        int(out["counters"][k] != v) for k, v in want["counters"].items()
    ) + sum(int(out["admission"][k] != v) for k, v in want["admission"].items())
    if "state" in out:
        bad["state_cells"] = want["ref"].state_mismatches(out["state"])
    else:   # the control: final arrays of the lower-precision replay
        bad["state_cells"] = want["ref"].state_mismatches(out["ref"].arrays())
    return bad
