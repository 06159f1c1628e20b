"""The check refuses a run whose timed path is broken underneath.

Run by hand on the CPU (a few minutes; ``testpaths`` keeps it out of the
repository's suite):

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/test_faults.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = ("t1-12k5.open", "t1-12k5.whatif")


def _run(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable] + argv, capture_output=True, text=True, env=env,
        timeout=900, cwd=os.path.dirname(os.path.dirname(HERE)),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _args(cell, seed):
    return ["--workload", cell, "--seed", str(seed), "--seconds", "3",
            "--trace", "0", "--rehearse"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    line = _run([os.path.join(HERE, "run.py")] + _args(cell, 2**31 + 17))
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_refused(cell, fault):
    line = _run([os.path.join(HERE, "faults.py"), fault] + _args(cell, 2**31 + 17))
    assert not line["correct"], line["checks"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
