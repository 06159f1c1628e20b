"""The control comes out as not correct where the program does not: at a
rehearsal size on the CPU, the reference computed with bfloat16
termination costs disagrees with the float32 reference on some decisions,
and the program on none.

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/test_control.py

On the chip, ``control.py`` reads the same numbers at the cells' own size.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("cell", ["t1-12k5.open", "t1-12k5.whatif"])
def test_control_fails_program_passes(cell):
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "control.py"), "--workload", cell,
         "--seconds", "4", "--seeds", str(2**32 + 5), "--rehearse"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=os.path.dirname(os.path.dirname(HERE)),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert all(v == 0 for v in line["program"].values()), line["program"]
    assert any(v > 0 for v in line["control"].values()), line["control"]
