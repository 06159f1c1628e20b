"""The batch cell on the CPU: a one-chip cell's policy is the one it always
was, a four-chip cell's carries a four-shard mesh, and a rehearsal of
``sharded-1m.batch`` over four virtual CPU devices (640 hosts, the stage-1
kernel interpreted) is correct, while the control and every planted fault
are not.

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/test_batch.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "sharded-1m.batch"
SEED = 2**32 + 11
#: four virtual devices for the four-chip cell
FOUR = dict(os.environ, JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=4")


def _run(argv, env=FOUR):
    out = subprocess.run([sys.executable] + argv, capture_output=True,
                         text=True, env=env, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


def _args(seed=SEED):
    return ["--workload", CELL, "--seed", str(seed), "--seconds", "3",
            "--trace", "0", "--rehearse"]


def test_one_chip_policy_unchanged():
    sys.path.insert(0, HERE)
    import jax

    import run

    repro = run.import_program(jax)
    config = run.load_json(HERE, "configs", "t1-12k5.json")
    p = config["policy"]
    want = repro.SchedulerPolicy(
        queue_capacity=p["queue_capacity"], admit_batch=p["admit_batch"],
        slo_target_s=p["slo_target_s"], max_retries=p["max_retries"],
        cost_kind=p["cost_kind"], period=p["period_s"], fused_screen=None,
    )
    got = run.policy_of(repro, config, False)
    assert got.mesh is None
    assert got == want == run.policy_of(repro, config, False, 1)


def test_four_chip_policy_has_a_four_shard_mesh():
    code = (
        "import sys, jax; sys.path.insert(0, %r); import run\n"
        "repro = run.import_program(jax)\n"
        "config = run.load_json(run.BENCH, 'configs', 'sharded-1m.json')\n"
        "p = run.policy_of(repro, config, False, 4)\n"
        "print(p.mesh.size, p.mesh.axis_names, p.queue_capacity)\n" % HERE)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=FOUR, timeout=300, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split() == ["4", "('hosts',)", "0"]


def test_every_seed_sends_the_same_jobs():
    """Two seeds send the same jobs at the same times: each job holds the
    same requests, in another order."""
    sys.path.insert(0, HERE)
    import numpy as np

    import fleetgen

    config = json.load(open(os.path.join(HERE, "configs", "sharded-1m.json")))
    traffic = json.load(open(os.path.join(HERE, "traffic", "t1-batch.json")))
    a = fleetgen.batch_jobs(config, traffic, 24.8, 20000, 3)
    b = fleetgen.batch_jobs(config, traffic, 24.8, 20000, SEED)
    sizes = np.diff(a.start)
    assert np.array_equal(a.start, b.start) and np.array_equal(a.t, b.t)
    assert np.array_equal(sizes, np.resize(traffic["job_sizes"], sizes.size))
    assert a.start[-1] >= 20000 and np.all(np.diff(a.t) >= 0)
    assert np.array_equal(a.arr.t, np.repeat(a.t, sizes))
    assert not np.array_equal(a.arr.life, b.arr.life)
    for k in range(len(a)):
        sl = slice(a.start[k], a.start[k + 1])
        assert sorted(zip(a.arr.flavor[sl], a.arr.pre[sl], a.arr.life[sl])) \
            == sorted(zip(b.arr.flavor[sl], b.arr.pre[sl], b.arr.life[sl]))


def test_rehearsal_is_correct_on_four_devices():
    line, err = _run([os.path.join(HERE, "run.py")] + _args())
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4 and line["failed"] == 0
    assert line["attempted"] > 0
    assert sorted(line["checks"]) == ["decisions", "departures", "refusals",
                                      "state_cells"]
    assert "compiles in window 0 " in err


def test_control_fails_program_passes():
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "control.py"), "--workload", CELL,
         "--seconds", "3", "--seeds", str(SEED), "--rehearse"],
        capture_output=True, text=True, timeout=900, env=FOUR, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert all(v == 0 for v in line["program"].values()), line["program"]
    assert line["control"]["decisions"] > 0, line["control"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered", "exchange_left_out"])
def test_fault_is_refused(fault):
    line, _ = _run([os.path.join(HERE, "faults.py"), fault] + _args())
    assert not line["correct"], line["checks"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
