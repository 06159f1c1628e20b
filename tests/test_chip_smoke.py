"""chip_smoke.py's phases at tiny sizes on the CPU.

The script itself refuses any machine without a TPU; its phase functions
are plain functions of a fleet size and a seed, so these tests run every
check the chip run makes (reference agreement, admission conservation,
preemption, bitwise replay, scan-vs-oracle parity, ensemble conservation)
at a few dozen hosts.  The sharded phase needs several devices and is
driven from tests/test_sharded_parity.py.
"""
from __future__ import annotations

import importlib.util
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_machine_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_reference_phase_tiny(smoke):
    out = smoke.reference_phase(n_hosts=24, n_requests=40, seed=1)
    assert out["placed"] > 0 and out["preemptions"] > 0


def test_served_phase_tiny(smoke):
    out = smoke.served_phase(n_hosts=48, n_requests=200, seed=1)
    assert out["placed"] > 0 and out["preemptions"] >= 1
    assert out["drains"] >= 2
    # On the CPU the default policy decides with the jnp screen.
    assert not out["kernel_in_drain"]


def test_scan_phase_tiny_matches_oracle(smoke):
    out = smoke.scan_phase(n_hosts=32, seed=1, duration_s=300.0)
    assert out["arrivals"] > 0 and out["preemptions"] > 0


def test_ensemble_phase_tiny(smoke):
    out = smoke.ensemble_phase(n_hosts=32, lanes=2, seed=1, duration_s=200.0)
    assert out["lanes"] == 2 and out["events"] > 0
