#!/usr/bin/env python3
"""Run one benchmark run with a fault planted in the timed path, to see the
check refuse it (``test_faults.py`` drives it on the CPU):

    python3 benchmarks/chip/faults.py <fault> --workload ... --rehearse

Faults, planted in the admission drain, in the scanned simulator and in
the direct entry's ``schedule_many`` scan:

* ``state_unchanged``: each decision step returns the fleet state it was
  given (the decision is reported, never applied);
* ``half_batch``: a drain attempts only the first half (rounded up) of
  the requests it selects; a ``schedule_many`` scan decides only the first
  half of its real rows and refuses the rest;
* ``answer_altered``: each decision reports the host after the one it
  placed the request on;
* ``exchange_left_out`` (a fleet sharded over several chips): the sharded
  screen's ``all_gather`` of the shards' shortlists hands every chip the
  first shard's in place of each, so the decision sees one shard's hosts
  (every chip still takes the same branches, so no collective waits on a
  chip that went another way).
"""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

import run

sys.path.insert(0, os.path.join(run.ROOT, "src"))

from repro.core import admission, jax_scheduler, scan_sim  # noqa: E402


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


def _half_rows(entry):
    def broken(state, req_res, *args, **kw):
        real = jnp.sum(req_res[:, 0] < admission.PAD_RES)
        keep = jnp.arange(req_res.shape[0]) < (real + 1) // 2
        res = jnp.where(keep[:, None], req_res, jnp.float32(admission.PAD_RES))
        return entry(state, res, *args, **kw)
    return broken


def _many_jits(make):
    """``schedule_many``'s two jitted entries rebuilt around a broken
    ``_many_entry``."""
    def plant_many():
        entry = make(jax_scheduler._many_entry)
        statics = jax_scheduler._STEP_STATICS
        jax_scheduler._many_donated = jax.jit(
            entry, static_argnames=statics, donate_argnums=(0,))
        jax_scheduler._many_kept = jax.jit(entry, static_argnames=statics)
    return plant_many


def _first_shard_only(all_gather):
    def broken(x, axis_name, *args, **kw):
        got = all_gather(x, axis_name, *args, **kw)
        return jnp.broadcast_to(got[:1], got.shape)
    return broken


def _patch(name, make, mods):
    def plant_one():
        for mod in mods:
            setattr(mod, name, make(getattr(mod, name)))
    return plant_one


_STEP = (admission, scan_sim, jax_scheduler)
FAULTS = {
    "state_unchanged": [_patch("_step_core", _state_unchanged, _STEP)],
    "answer_altered": [_patch("_step_core", _answer_altered, _STEP)],
    "half_batch": [_patch("queue_select", _half_batch, (admission, scan_sim)),
                   _many_jits(_half_rows)],
    "exchange_left_out": [_patch("all_gather", _first_shard_only, (jax.lax,))],
}


def plant(fault: str) -> None:
    for plant_one in FAULTS[fault]:
        plant_one()


if __name__ == "__main__":
    plant(sys.argv[1])
    sys.exit(run.main(sys.argv[2:]))
