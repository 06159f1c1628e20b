"""Ahead-of-time compiles of the main path's Pallas kernels for TPU v5e.

The interpret-mode parity suites (tests/test_sched_screen.py,
tests/test_kernels_sched.py) prove the kernels compute the right answer, but
interpret mode lowers to plain HLO and never meets the TPU compiler
(Mosaic).  These tests compile every kernel of the decision path with
``interpret=False`` for a *described* v5e chip — the TPU compiler ships with
jaxlib and needs no attached device — at fleet widths (N = 65 536 hosts and
one N that is not a multiple of the 128-host tile, K = 8 slots, D = 3
resource dims, a 64-candidate shortlist).  Each test asserts the compiled
executable holds the kernel as a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time, so
under pytest-xdist only the worker that runs this file may touch it.  Keep
every chip-compile case in this one file for the same reason.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.jax_scheduler import subset_masks
from repro.core.screen_math import N_CONSTS
from repro.kernels.sched_screen import (
    sched_screen,
    sched_screen_consts,
    sched_screen_topm,
)
from repro.kernels.sched_weigh import sched_weigh, sched_weigh_gathered

D = 3
M = 64
#: default policy weighers, and the 5-slot form with the churn term on
MULT = (1.0, 1.0, 0.0, 0.0)
MULT_CHURN = (1.0, 1.0, 0.0, 0.0, 2.0)
CHURN_THRESHOLD = 0.5
#: fleet widths: a tile multiple and one that pads the last tile
N_FLEET = (65_536, 100_000)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _screen_shapes(sharding, n, k, extras):
    """The 11 fleet/request operands of the stage-1 kernels, plus the churn
    row, zone row and excluded-zone scalar when ``extras``."""
    s = lambda shape, dtype: _shape(sharding, shape, dtype)  # noqa: E731
    shapes = [
        s((n, D), jnp.float32), s((n, D), jnp.float32), s((n,), jnp.bool_),
        s((n,), jnp.int32), s((n,), jnp.float32),
        s((n, k, D), jnp.float32), s((n, k), jnp.float32),
        s((n, k), jnp.bool_),
        s((D,), jnp.float32), s((), jnp.bool_), s((), jnp.int32),
    ]
    if extras:
        shapes += [s((n,), jnp.float32), s((n,), jnp.int32), s((), jnp.int32)]
    return shapes


def _extras_kw(extras, a):
    """Keyword operands for the optional failure-domain inputs."""
    if not extras:
        return dict(weigher_multipliers=MULT)
    return dict(
        weigher_multipliers=MULT_CHURN, churn=a[11],
        churn_threshold=CHURN_THRESHOLD, host_zone=a[12], exclude_zone=a[13],
    )


def _compiled_text(fn, shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize(
    "n,k,extras",
    [(65_536, 8, False), (100_000, 8, False), (65_536, 12, False),
     (65_536, 8, True)],
)
def test_sched_screen_compiles_for_v5e(one_chip, n, k, extras):
    def fn(*a):
        return sched_screen(
            *a[:11], require_free_slot=True, m_keep=M + 1, interpret=False,
            **_extras_kw(extras, a),
        )

    text = _compiled_text(fn, _screen_shapes(one_chip, n, k, extras))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", N_FLEET)
def test_sched_screen_consts_compiles_for_v5e(one_chip, n):
    def fn(*a):
        return sched_screen_consts(
            *a[:11], require_free_slot=True, interpret=False,
            **_extras_kw(True, a),
        )

    text = _compiled_text(fn, _screen_shapes(one_chip, n, 8, True))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", N_FLEET)
def test_sched_screen_topm_compiles_for_v5e(one_chip, n):
    def fn(*a):
        return sched_screen_topm(
            *a[:11], a[14], require_free_slot=True, m_keep=M + 1,
            interpret=False, **_extras_kw(True, a),
        )

    shapes = _screen_shapes(one_chip, n, 8, True)
    shapes.append(_shape(one_chip, (N_CONSTS,), jnp.float32))
    text = _compiled_text(fn, shapes)
    assert "tpu_custom_call" in text


def _weigh_shapes(sharding, n, k):
    s = lambda shape, dtype: _shape(sharding, shape, dtype)  # noqa: E731
    return [
        s((n, D), jnp.float32), s((n, k, D), jnp.float32),
        s((n, k), jnp.float32), s((n, k), jnp.float32), s((D,), jnp.float32),
    ]


@pytest.mark.parametrize("n", N_FLEET)
def test_sched_weigh_compiles_for_v5e(one_chip, n):
    masks = subset_masks(8)

    def fn(*a):
        return sched_weigh(*a, masks, interpret=False)

    text = _compiled_text(fn, _weigh_shapes(one_chip, n, 8))
    assert "tpu_custom_call" in text


def test_sched_weigh_gathered_compiles_for_v5e(one_chip):
    masks = subset_masks(8)

    def fn(*a):
        return sched_weigh_gathered(*a, masks, interpret=False)

    text = _compiled_text(fn, _weigh_shapes(one_chip, M, 8))
    assert "tpu_custom_call" in text
