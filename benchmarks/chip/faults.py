#!/usr/bin/env python3
"""Run one benchmark run with a fault planted in the timed path, to see the
check refuse it (``test_faults.py`` drives it on the CPU):

    python3 benchmarks/chip/faults.py <fault> --workload ... --rehearse

Faults, planted both in the admission drain and in the scanned simulator:

* ``state_unchanged``: each decision step returns the fleet state it was
  given (the decision is reported, never applied);
* ``half_batch``: a drain attempts only the first half (rounded up) of
  the requests it selects;
* ``answer_altered``: each decision reports the host after the one it
  placed the request on.

A one-chip cell has no exchange between chips to leave out.
"""
from __future__ import annotations

import os
import sys

import jax.numpy as jnp

import run

sys.path.insert(0, os.path.join(run.ROOT, "src"))

from repro.core import admission, scan_sim  # noqa: E402


def _state_unchanged(step):
    def broken(state, *args, **kw):
        _, outs = step(state, *args, **kw)
        return state, outs
    return broken


def _answer_altered(step):
    def broken(state, *args, **kw):
        state2, (h, slot, ok, kill, fb, mg) = step(state, *args, **kw)
        n = state.free_f.shape[0]
        return state2, ((h + ok.astype(h.dtype)) % n, slot, ok, kill, fb, mg)
    return broken


def _half_batch(select):
    def broken(q, batch, *args, **kw):
        idx, take = select(q, batch, *args, **kw)
        keep = (take.sum() + 1) // 2
        return idx, take & (jnp.arange(idx.shape[0]) < keep)
    return broken


FAULTS = {
    "state_unchanged": ("_step_core", _state_unchanged),
    "answer_altered": ("_step_core", _answer_altered),
    "half_batch": ("queue_select", _half_batch),
}


def plant(fault: str) -> None:
    name, make = FAULTS[fault]
    for mod in (admission, scan_sim):
        setattr(mod, name, make(getattr(mod, name)))


if __name__ == "__main__":
    plant(sys.argv[1])
    sys.exit(run.main(sys.argv[2:]))
