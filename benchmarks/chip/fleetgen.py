"""Seeded generators of the benchmark's inputs: the prefilled fleet and the
arrival trace.  Plain data only (numpy arrays and tuples); ``served`` and
``whatif`` turn it into the program's inputs, and ``reference`` into its
own arrays.

The prefill rules and the request stream follow the scheduler's bring-up
smoke (``saturated_fleet`` / ``prefill_fleet``) and the paper's §4.4
lifetime law (exponential, mean 5 400 s, truncated to [600 s, 18 000 s]),
copied here so that the yardstick does not move when the program does.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Tuple

import numpy as np

#: simulated time of the prefill's "now": prefilled ages count back from it
#: and every trace starts at it (an integer, exact in float32)
T0 = 1_000_000.0
#: every prefill and every request set is drawn from this seed; a run's
#: seed only orders them, so that every seed asks for the same work
BASE_SEED = 0
#: a run's seed shuffles requests within blocks of this many, so that any
#: prefix of whole blocks holds the same requests for every seed
ORDER_BLOCK = 256


@dataclasses.dataclass
class Fleet:
    """A prefilled fleet as plain arrays: ``n`` hosts of capacity ``cap``;
    instance ``i`` (id ``ids[i]``) of size ``res[i]`` runs on ``host[i]``
    since ``start[i]``."""

    n: int
    cap: np.ndarray        # (D,) float64
    k_slots: int
    ids: List[str]
    host: np.ndarray       # (I,) int64
    res: np.ndarray        # (I, D) float64
    pre: np.ndarray        # (I,) bool
    start: np.ndarray      # (I,) float64

    def free_vcpus(self) -> float:
        used = np.zeros(self.n)
        np.add.at(used, self.host, self.res[:, 0])
        return float(self.n * self.cap[0] - used.sum())


def flavor_table(config) -> Tuple[List[str], np.ndarray, np.ndarray]:
    """Names, (F, D) sizes and probabilities of the configuration's flavours."""
    names = list(config["flavors"])
    sizes = np.array([config["flavors"][k] for k in names], np.float64)
    probs = np.array(config["flavor_probs"], np.float64)
    return names, sizes, probs


def _fixed_rule(config, seed: int):
    """``per_node`` instances of one flavour on every node, each preemptible
    with probability ``preemptible_fraction`` (at most ``k_max`` per node;
    a node left with none gets its first made preemptible), started a seeded
    whole number of minutes in ``age_minutes`` before T0."""
    p = config["prefill"]
    size = np.asarray(config["flavors"][p["flavor"]], np.float64)
    lo, hi = p["age_minutes"]
    rng = np.random.default_rng(seed)
    rows = []
    for h in range(config["hosts"]):
        n_pre, first = 0, len(rows)
        for _ in range(p["per_node"]):
            pre = bool(rng.random() < p["preemptible_fraction"]) and n_pre < p["k_max"]
            n_pre += int(pre)
            rows.append([h, pre, T0 - float(rng.integers(lo, hi)) * 60.0, size])
        if n_pre == 0:
            rows[first][1] = True
    return rows


def _fill_rule(config, seed: int):
    """Fill every node with seeded flavours drawn from ``flavor_probs`` (the
    prefill's own mix) until the next one would not fit or the node holds
    ``k_slots`` instances; each preemptible with probability
    ``preemptible_fraction``; ages as in the fixed rule."""
    p = config["prefill"]
    names = list(p["flavor_probs"])
    sizes = np.array([config["flavors"][k] for k in names], np.float64)
    probs = np.array([p["flavor_probs"][k] for k in names], np.float64)
    probs = probs / probs.sum()
    cap = np.asarray(config["node"], np.float64)
    lo, hi = p["age_minutes"]
    n, k = config["hosts"], config["k_slots"]
    rng = np.random.default_rng(seed)
    flav = rng.choice(len(names), size=(n, k), p=probs)
    pre = rng.random((n, k)) < p["preemptible_fraction"]
    age = rng.integers(lo, hi, size=(n, k)).astype(np.float64) * 60.0
    used = np.zeros((n, cap.size))
    active = np.ones(n, bool)
    take = np.zeros((n, k), bool)
    for c in range(k):
        size = sizes[flav[:, c]]
        active &= np.all(used + size <= cap, axis=1)
        take[:, c] = active
        used += size * active[:, None]
    hs, cs = np.nonzero(take)          # host-major, in fill order
    return [[int(h), bool(pre[h, c]), T0 - age[h, c], sizes[flav[h, c]]]
            for h, c in zip(hs, cs)]


_RULES = {"fixed": _fixed_rule, "fill": _fill_rule}


def prefill(config, seed: int) -> Fleet:
    """The configuration's prefilled fleet, drawn from ``BASE_SEED`` (less
    the first instance of a seeded ``free_fraction`` of the nodes), with its
    hosts in the order ``seed`` gives: every seed holds the same hosts."""
    p = config["prefill"]
    rows = _RULES[p["rule"]](config, BASE_SEED)
    n = config["hosts"]
    rng = np.random.default_rng(BASE_SEED + 1)
    drop = rng.random(n) < p["free_fraction"]
    keep, seen = [], set()
    for i, row in enumerate(rows):
        h = row[0]
        if drop[h] and h not in seen:
            seen.add(h)
            continue
        keep.append(i)
    rows = [rows[i] for i in keep]
    place = np.random.default_rng(np.random.SeedSequence([seed, 11])).permutation(n)
    return Fleet(
        n=n,
        cap=np.asarray(config["node"], np.float64),
        k_slots=int(config["k_slots"]),
        ids=[f"x{keep[j]}" for j in range(len(rows))],
        host=place[np.array([r[0] for r in rows], np.int64)],
        res=np.array([r[3] for r in rows], np.float64).reshape(-1, len(config["node"])),
        pre=np.array([r[1] for r in rows], bool),
        start=np.array([r[2] for r in rows], np.float64),
    )


def truncated_exp_mean(mean: float, lo: float, hi: float) -> float:
    """Mean of an exponential with ``mean`` conditioned on [lo, hi]."""
    a, b = math.exp(-lo / mean), math.exp(-hi / mean)
    return mean + (lo * a - hi * b) / (a - b)


def little_rate(config, fleet: Fleet) -> float:
    """Arrivals per simulated second that the prefill's free vCPUs sustain
    (Little's law): free vCPU / (mean vCPU per arrival x mean lifetime)."""
    _, sizes, probs = flavor_table(config)
    life = config["lifetime_s"]
    mean_life = truncated_exp_mean(life["mean"], life["min"], life["max"])
    return fleet.free_vcpus() / (float(probs @ sizes[:, 0]) * mean_life)


@dataclasses.dataclass
class Arrivals:
    """A seeded request stream in simulated time, sorted by ``t``: request
    ``j`` asks for ``res[j]`` (flavour ``flavor[j]``) at ``t[j]``, is
    preemptible or not, and runs ``life[j]`` seconds once placed (whole
    seconds throughout, so every sum stays exact in float32)."""

    t: np.ndarray        # (R,) float64, whole seconds >= T0
    flavor: np.ndarray   # (R,) int64
    res: np.ndarray      # (R, D) float64
    pre: np.ndarray      # (R,) bool
    life: np.ndarray     # (R,) float64, whole seconds

    def __len__(self) -> int:
        return int(self.t.shape[0])


def _block_shuffle(rng, count: int) -> np.ndarray:
    """A permutation of ``range(count)`` that keeps each block of
    ``ORDER_BLOCK`` consecutive positions in place as a set."""
    order = np.arange(count)
    for lo in range(0, count, ORDER_BLOCK):
        hi = min(count, lo + ORDER_BLOCK)
        order[lo:hi] = lo + rng.permutation(hi - lo)
    return order


def arrivals(config, rate: float, count: int, seed: int) -> Arrivals:
    """``count`` requests of a Poisson stream at ``rate`` per simulated
    second, with the configuration's flavour mix, preemptible share and
    lifetime law; times floored to whole seconds.  The gaps between
    arrivals and the requests are drawn from ``BASE_SEED``; ``seed``
    shuffles the gaps, and apart from them the requests, within each block
    of ``ORDER_BLOCK``: every seed sends the same requests at the same
    pace, in another order."""
    _, sizes, probs = flavor_table(config)
    life = config["lifetime_s"]
    rng = np.random.default_rng(np.random.SeedSequence([BASE_SEED, 7]))
    gaps = rng.exponential(1.0 / rate, count)
    flavor = rng.choice(len(probs), size=count, p=probs / probs.sum())
    pre = rng.random(count) < config["preemptible_fraction"]
    # exponential truncated to [min, max] by rejection, as the simulator draws
    draw = rng.exponential(life["mean"], count)
    bad = (draw < life["min"]) | (draw > life["max"])
    while bad.any():
        draw[bad] = rng.exponential(life["mean"], int(bad.sum()))
        bad = (draw < life["min"]) | (draw > life["max"])
    mix = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    t = T0 + np.floor(np.cumsum(gaps[_block_shuffle(mix, count)]))
    req = _block_shuffle(mix, count)
    flavor, pre, draw = flavor[req], pre[req], draw[req]
    return Arrivals(
        t=t, flavor=flavor.astype(np.int64), res=sizes[flavor], pre=pre,
        life=np.maximum(1.0, np.round(draw)),
    )
