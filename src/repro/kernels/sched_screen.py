"""Pallas TPU kernel: fused stage-1 screen + on-chip top-M shortlist.

One pass over the fleet replaces the pure-jnp stage-1 pipeline (dual-view
fit mask, exact full-subset feasibility, sorted-prefix termination-cost
bounds, optimistic ``omega_ub``, global ``lax.top_k``), whose separate
O(N·K) passes each round-trip the full host arrays through HBM — the
dominant latency term at 10^5 hosts once stage 2 only enumerates a
shortlist.  Here every term is computed per 128-host tile from VMEM via the
*shared* bounds math in ``repro.core.screen_math`` (both screens execute the
same functions, so shortlist decisions stay bit-exact), and the only HBM
writes are the (M+1,) shortlist plus 10 normalization scalars.

Structure (grid = (2, N/T), sequential on TPU):

  phase 0   fold the global weigher-normalization constants (termination
            cost envelope min/max + raw base-term min/max over the valid
            set) tile-by-tile into SMEM scratch — min/max are
            reassociation-free, so the folded constants match the jnp
            reductions bitwise;
  phase 1   recompute the tile's screen terms, assemble ``omega_ub`` from
            the SMEM constants, and fold (score, host-index) pairs into a
            running top-M kept sorted in the output VMEM block by a bitonic
            lane network (``pltpu.roll`` partner exchanges).  Ties order by
            lowest host index — exactly ``lax.top_k``'s tie rule, so the
            emitted shortlist equals the oracle's up to nothing at all.

The buffer holds S = next_pow2(m_keep + T) lanes: each step concatenates the
previous top-(S-T) with the tile's T candidates and re-sorts, so the keep
region always contains the true running top-(S-T) — no reset logic.  Entry
``m_keep-1`` (= M) is the best *non-shortlisted* ``omega_ub`` and its index:
precisely the (u, j_u) pair the admissibility fallback check needs.

VMEM per step at K=8, D=4, T=128: res tile (8,4,128)f32 16 KB + buffer
2×(1,256) + odds and ends ≈ 25 KB — far inside the v5e budget; T=128 keeps
the kernel latency-bound like ``sched_weigh``.

Oracle: ``repro.core.jax_scheduler.screen_terms`` + ``_decision_core``'s
stage-1 assembly (same shared math).  Validated in interpret mode by
tests/test_sched_screen.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.screen_math import (
    EPS,
    N_CONSTS,
    NEG_INF,
    POS_INF,
    ScreenConsts,
    _m_churn,
    base_from_consts,
    inv_span,
    omega_of,
    screen_bounds_rows,
    total_rows,
)

TILE_HOSTS = 128
#: index sentinel for empty buffer slots — larger than any real host index,
#: so initial entries sort after every real candidate (ties break low-index).
IDX_SENTINEL = 2 ** 30


def _fold_top(scores_ref, idx_ref, tile_scores, tile_idx, s_buf, tile):
    """Fold a tile's (1, T) candidates into the sorted (1, S) running top.

    Concatenate the previous top-(S-T) with the new tile and re-sort
    descending by (score, -index) with a bitonic lane network.  Partner
    lookup ``x[i ^ j]`` is two ``pltpu.roll``s selected by the j-bit; the
    comparator is total (indices are unique), so the result is deterministic
    and matches ``lax.top_k`` tie ordering."""
    keep = s_buf - tile
    scores = jnp.concatenate([scores_ref[...][:, :keep], tile_scores], axis=1)
    idx = jnp.concatenate([idx_ref[...][:, :keep], tile_idx], axis=1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, s_buf), 1)
    k = 2
    while k <= s_buf:
        j = k // 2
        while j >= 1:
            bit0 = (lane & j) == 0

            def partner(x):
                return jnp.where(
                    bit0,
                    pltpu.roll(x, s_buf - j, axis=1),
                    pltpu.roll(x, j, axis=1),
                )

            ps, pi = partner(scores), partner(idx)
            self_first = (scores > ps) | ((scores == ps) & (idx < pi))
            want_first = ((lane & k) == 0) == bit0
            take_self = self_first == want_first
            scores = jnp.where(take_self, scores, ps)
            idx = jnp.where(take_self, idx, pi)
            j //= 2
        k *= 2
    scores_ref[...] = scores
    idx_ref[...] = idx


def _tile_stage1(
    free_f_ref, free_n_ref, sched_ref, domain_ref, slow_ref,
    res_ref, cost_ref, valid_ref, req_ref, pre_ref, rdom_ref,
    *, require_free_slot, churn_ref=None, churn_threshold=None,
    zone_ref=None, excl_ref=None,
):
    """One tile's stage-1 screen terms from VMEM refs — the shared
    ``screen_math`` bounds plus the dual-view filtering (same formulas as
    ``_decision_core``).  Returns ``(valid, cost_lb, cost_ub, over_raw,
    pack_raw, strag_raw, churn_raw)``, each (T,)-shaped (``churn_raw`` is
    ``None`` without a churn column).  ONE definition executed by all three
    kernels below (2-phase fused, consts-only, topm-only), which is what
    keeps the split phases bit-identical to the fused pass.

    ``churn_ref`` is the optional (1, T) per-host learned zone-churn rate ẑ;
    a static ``churn_threshold`` applies the hot-zone steering filter to
    preemptible requests (same gate as ``_stage1_rows``).  ``zone_ref`` is
    the optional (1, T) per-host zone-id column and ``excl_ref`` the (1, 1)
    per-request excluded-zone scalar: relocation re-placements hard-filter
    every host of the zone they are fleeing (integer compare, so the gate is
    trivially bit-exact vs ``_stage1_rows``); a negative scalar disables."""
    k = res_ref.shape[0]
    pre = pre_ref[0, 0] != 0
    rdom = rdom_ref[0, 0]
    free_f = free_f_ref[...]                                     # (D, T)
    req = req_ref[...]                                           # (D, 1)
    validf = valid_ref[...]                                      # (K, T)

    # ---- shared stage-1 bounds math on slot-major rows ----------------------
    res_rows = [res_ref[i] * validf[i][None, :] for i in range(k)]
    cost_rows = [
        jnp.where(validf[i] > 0.5, cost_ref[i], POS_INF) for i in range(k)
    ]
    total = total_rows(
        [jnp.where(validf[i] > 0.5, cost_ref[i], 0.0) for i in range(k)]
    )
    need = req - free_f                                          # (D, T)
    feasible, overcommitted, cost_lb, cost_ub = screen_bounds_rows(
        need, res_rows, cost_rows, total
    )

    # ---- dual-view filtering (same formula as _decision_core) ---------------
    view = jnp.where(pre, free_f, free_n_ref[...])
    fits = jnp.all(view >= req - EPS, axis=0)                    # (T,)
    fits &= sched_ref[...][0] > 0.5
    fits &= (rdom < 0) | (domain_ref[...][0] == rdom)
    if zone_ref is not None and excl_ref is not None:
        excl = excl_ref[0, 0]
        fits &= (excl < 0) | (zone_ref[...][0] != excl)
    # The gates below are boolean algebra, not ``jnp.where``: Mosaic cannot
    # lower a select whose value operands are both boolean vectors.
    if churn_threshold is not None and churn_ref is not None:
        fits &= ~pre | (churn_ref[...][0] <= jnp.float32(churn_threshold))
    if require_free_slot:
        has_free = jnp.min(validf, axis=0) < 0.5
        fits &= ~pre | has_free
    cost_lb = jnp.where(pre, 0.0, cost_lb)
    cost_ub = jnp.where(pre, 0.0, cost_ub)
    feasible = (pre & fits) | (~pre & feasible)
    valid = fits & feasible

    over_raw = jnp.where(overcommitted, -1.0, 0.0)
    pack_raw = -jnp.sum(free_f, axis=0)
    strag_raw = -slow_ref[...][0]
    churn_raw = None if churn_ref is None else -churn_ref[...][0]
    return valid, cost_lb, cost_ub, over_raw, pack_raw, strag_raw, churn_raw


def _split_refs(refs, n_extra, has_churn, has_zone):
    """Unpack a kernel's positional refs: the 11 fleet/request inputs, the
    optional churn input, the optional zone-row + excluded-zone pair, then
    ``n_extra`` output/scratch refs.  Returns
    ``(fleet_refs, churn_ref, zone_ref, excl_ref, extra_refs)``."""
    fleet = refs[:11]
    n_in = 11
    churn_ref = zone_ref = excl_ref = None
    if has_churn:
        churn_ref = refs[n_in]
        n_in += 1
    if has_zone:
        zone_ref = refs[n_in]
        excl_ref = refs[n_in + 1]
        n_in += 2
    return fleet, churn_ref, zone_ref, excl_ref, refs[n_in:]


def _fold_consts(smem, valid, cost_lb, cost_ub, raws):
    """One tile's constants fold into SMEM: the termination-cost envelope
    always, each raw base term only when its multiplier is on (identical
    gating to ``consts_of``).  ``raws`` pairs (multiplier, raw-or-None) in
    ScreenConsts slot order."""
    smem[0] = jnp.minimum(smem[0], jnp.min(jnp.where(valid, cost_lb, POS_INF)))
    smem[1] = jnp.maximum(smem[1], jnp.max(jnp.where(valid, cost_ub, NEG_INF)))
    for slot, (on, raw) in enumerate(raws):
        if on and raw is not None:
            smem[2 + 2 * slot] = jnp.minimum(
                smem[2 + 2 * slot], jnp.min(jnp.where(valid, raw, POS_INF))
            )
            smem[3 + 2 * slot] = jnp.maximum(
                smem[3 + 2 * slot], jnp.max(jnp.where(valid, raw, NEG_INF))
            )


def _kernel(
    *refs,
    multipliers, require_free_slot, churn_threshold, tile, s_buf, has_churn,
    has_zone,
):
    m_term = multipliers[1]
    m_churn = _m_churn(multipliers)
    (fleet, churn_ref, zone_ref, excl_ref,
     (scores_ref, idx_ref, consts_ref, smem)) = _split_refs(
        refs, 4, has_churn, has_zone
    )
    phase = pl.program_id(0)
    t = pl.program_id(1)
    (valid, cost_lb, cost_ub, over_raw, pack_raw, strag_raw,
     churn_raw) = _tile_stage1(
        *fleet,
        require_free_slot=require_free_slot,
        churn_ref=churn_ref, churn_threshold=churn_threshold,
        zone_ref=zone_ref, excl_ref=excl_ref,
    )

    # ---- phase 0: fold normalization constants into SMEM --------------------
    @pl.when((phase == 0) & (t == 0))
    def _():
        for i in range(N_CONSTS // 2):
            smem[2 * i] = jnp.float32(POS_INF)
            smem[2 * i + 1] = jnp.float32(NEG_INF)

    @pl.when(phase == 0)
    def _():
        _fold_consts(
            smem, valid, cost_lb, cost_ub,
            [(multipliers[0], over_raw), (multipliers[2], pack_raw),
             (multipliers[3], strag_raw), (m_churn, churn_raw)],
        )

    # ---- phase 1: omega_ub from the constants + running top-M ---------------
    @pl.when((phase == 1) & (t == 0))
    def _():
        scores_ref[...] = jnp.full((1, s_buf), NEG_INF, jnp.float32)
        idx_ref[...] = jnp.full((1, s_buf), IDX_SENTINEL, jnp.int32)

    @pl.when(phase == 1)
    def _():
        consts = ScreenConsts(*(smem[i] for i in range(N_CONSTS)))
        base = base_from_consts(
            multipliers, over_raw, pack_raw, strag_raw, consts,
            churn_raw=churn_raw,
        )
        ispan = inv_span(consts.c_lo, consts.c_hi)
        opt_cost = cost_lb if m_term >= 0 else cost_ub
        omega_ub = omega_of(opt_cost, base, valid, consts, ispan, m_term)
        gidx = t * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        _fold_top(scores_ref, idx_ref, omega_ub[None, :], gidx, s_buf, tile)
        consts_ref[...] = consts.pack()[None, :]


def _consts_kernel(
    *refs, multipliers, require_free_slot, churn_threshold, has_churn,
    has_zone,
):
    """Phase 0 alone: fold the 10 normalization constants over the fleet
    (identical folds to ``_kernel``'s phase 0) and emit them — the
    per-shard half of the split the sharded fused screen needs, so the
    mesh can pmin/pmax-merge constants BEFORE any omega is scored."""
    m_churn = _m_churn(multipliers)
    fleet, churn_ref, zone_ref, excl_ref, (consts_ref, smem) = _split_refs(
        refs, 2, has_churn, has_zone
    )
    t = pl.program_id(0)
    (valid, cost_lb, cost_ub, over_raw, pack_raw, strag_raw,
     churn_raw) = _tile_stage1(
        *fleet,
        require_free_slot=require_free_slot,
        churn_ref=churn_ref, churn_threshold=churn_threshold,
        zone_ref=zone_ref, excl_ref=excl_ref,
    )

    @pl.when(t == 0)
    def _():
        for i in range(N_CONSTS // 2):
            smem[2 * i] = jnp.float32(POS_INF)
            smem[2 * i + 1] = jnp.float32(NEG_INF)

    _fold_consts(
        smem, valid, cost_lb, cost_ub,
        [(multipliers[0], over_raw), (multipliers[2], pack_raw),
         (multipliers[3], strag_raw), (m_churn, churn_raw)],
    )
    consts_ref[...] = jnp.stack([smem[i] for i in range(N_CONSTS)])[None, :]


def _topm_kernel(
    *refs,
    multipliers, require_free_slot, churn_threshold, tile, s_buf, has_churn,
    has_zone,
):
    """Phase 1 alone, scoring against EXTERNAL constants (``consts_in_ref``,
    e.g. the mesh-merged ``ScreenConsts``): recompute the tile's screen
    terms, assemble ``omega_ub``, fold the running top-M — the same ops as
    ``_kernel``'s phase 1 reading merged constants instead of SMEM."""
    m_term = multipliers[1]
    (fleet, churn_ref, zone_ref, excl_ref,
     (consts_in_ref, scores_ref, idx_ref)) = _split_refs(
        refs, 3, has_churn, has_zone
    )
    t = pl.program_id(0)
    (valid, cost_lb, cost_ub, over_raw, pack_raw, strag_raw,
     churn_raw) = _tile_stage1(
        *fleet,
        require_free_slot=require_free_slot,
        churn_ref=churn_ref, churn_threshold=churn_threshold,
        zone_ref=zone_ref, excl_ref=excl_ref,
    )

    @pl.when(t == 0)
    def _():
        scores_ref[...] = jnp.full((1, s_buf), NEG_INF, jnp.float32)
        idx_ref[...] = jnp.full((1, s_buf), IDX_SENTINEL, jnp.int32)

    consts = ScreenConsts(*(consts_in_ref[0, i] for i in range(N_CONSTS)))
    base = base_from_consts(
        multipliers, over_raw, pack_raw, strag_raw, consts, churn_raw=churn_raw
    )
    ispan = inv_span(consts.c_lo, consts.c_hi)
    opt_cost = cost_lb if m_term >= 0 else cost_ub
    omega_ub = omega_of(opt_cost, base, valid, consts, ispan, m_term)
    gidx = t * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    _fold_top(scores_ref, idx_ref, omega_ub[None, :], gidx, s_buf, tile)


def _in_specs(k, d, tile, has_churn, has_zone):
    """The fleet/request BlockSpec list shared by all three kernels (the
    host axis is the grid's LAST dimension, so the index maps take the
    final program id as the tile index).  ``has_churn`` appends the (1, T)
    churn-row spec; ``has_zone`` the (1, T) zone-id row plus the (1, 1)
    excluded-zone scalar."""
    host = lambda *ids: (0, ids[-1])
    fixed = lambda *ids: (0, 0)
    specs = [
        pl.BlockSpec((d, tile), host),
        pl.BlockSpec((d, tile), host),
        pl.BlockSpec((1, tile), host),
        pl.BlockSpec((1, tile), host),
        pl.BlockSpec((1, tile), host),
        pl.BlockSpec((k, d, tile), lambda *ids: (0, 0, ids[-1])),
        pl.BlockSpec((k, tile), host),
        pl.BlockSpec((k, tile), host),
        pl.BlockSpec((d, 1), fixed),
        pl.BlockSpec((1, 1), fixed),
        pl.BlockSpec((1, 1), fixed),
    ]
    if has_churn:
        specs.append(pl.BlockSpec((1, tile), host))
    if has_zone:
        specs.append(pl.BlockSpec((1, tile), host))
        specs.append(pl.BlockSpec((1, 1), fixed))
    return specs


def _decode_extras(args):
    """Recover the static (has_churn, has_zone) pair from an ``args`` tuple
    built by ``_prep_inputs``: 11 fleet/request inputs, +1 churn row, +2
    zone row + excluded-zone scalar."""
    extras = len(args) - 11
    return extras in (1, 3), extras >= 2


@functools.partial(
    jax.jit,
    static_argnames=(
        "multipliers", "require_free_slot", "churn_threshold", "s_buf",
        "tile", "interpret",
    ),
)
def _sched_screen_padded(
    args, multipliers, require_free_slot, churn_threshold, s_buf, tile,
    interpret,
):
    has_churn, has_zone = _decode_extras(args)
    k, d, n = args[5].shape
    fixed = lambda *ids: (0, 0)
    kern = functools.partial(
        _kernel,
        multipliers=multipliers,
        require_free_slot=require_free_slot,
        churn_threshold=churn_threshold,
        tile=tile,
        s_buf=s_buf,
        has_churn=has_churn,
        has_zone=has_zone,
    )
    return pl.pallas_call(
        kern,
        grid=(2, n // tile),
        in_specs=_in_specs(k, d, tile, has_churn, has_zone),
        out_specs=(
            pl.BlockSpec((1, s_buf), fixed),
            pl.BlockSpec((1, s_buf), fixed),
            pl.BlockSpec((1, N_CONSTS), fixed),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((1, s_buf), jnp.float32),
            jax.ShapeDtypeStruct((1, s_buf), jnp.int32),
            jax.ShapeDtypeStruct((1, N_CONSTS), jnp.float32),
        ),
        scratch_shapes=[pltpu.SMEM((N_CONSTS,), jnp.float32)],
        interpret=interpret,
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=(
        "multipliers", "require_free_slot", "churn_threshold", "tile",
        "interpret",
    ),
)
def _sched_consts_padded(
    args, multipliers, require_free_slot, churn_threshold, tile, interpret,
):
    has_churn, has_zone = _decode_extras(args)
    k, d, n = args[5].shape
    fixed = lambda t: (0, 0)
    kern = functools.partial(
        _consts_kernel,
        multipliers=multipliers,
        require_free_slot=require_free_slot,
        churn_threshold=churn_threshold,
        has_churn=has_churn,
        has_zone=has_zone,
    )
    return pl.pallas_call(
        kern,
        grid=(n // tile,),
        in_specs=_in_specs(k, d, tile, has_churn, has_zone),
        out_specs=pl.BlockSpec((1, N_CONSTS), fixed),
        out_shape=jax.ShapeDtypeStruct((1, N_CONSTS), jnp.float32),
        scratch_shapes=[pltpu.SMEM((N_CONSTS,), jnp.float32)],
        interpret=interpret,
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=(
        "multipliers", "require_free_slot", "churn_threshold", "s_buf",
        "tile", "interpret",
    ),
)
def _sched_topm_padded(
    args, consts, multipliers, require_free_slot, churn_threshold, s_buf,
    tile, interpret,
):
    has_churn, has_zone = _decode_extras(args)
    k, d, n = args[5].shape
    fixed = lambda t: (0, 0)
    kern = functools.partial(
        _topm_kernel,
        multipliers=multipliers,
        require_free_slot=require_free_slot,
        churn_threshold=churn_threshold,
        tile=tile,
        s_buf=s_buf,
        has_churn=has_churn,
        has_zone=has_zone,
    )
    return pl.pallas_call(
        kern,
        grid=(n // tile,),
        in_specs=_in_specs(k, d, tile, has_churn, has_zone)
        + [pl.BlockSpec((1, N_CONSTS), fixed)],
        out_specs=(
            pl.BlockSpec((1, s_buf), fixed),
            pl.BlockSpec((1, s_buf), fixed),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((1, s_buf), jnp.float32),
            jax.ShapeDtypeStruct((1, s_buf), jnp.int32),
        ),
        interpret=interpret,
    )(*args, consts)


def _prep_inputs(
    free_f, free_n, schedulable, domain, slow,
    inst_res, inst_cost, inst_valid,
    req_res, req_preemptible, req_domain,
    tile: int,
    churn=None,
    host_zone=None,
    exclude_zone=None,
):
    """Dtype-normalize, pad the host axis to the tile, and transpose to the
    kernels' slot-major layout.  Padding rows are unschedulable, so they
    can never outrank a real host.  An optional ``churn`` column (per-host
    ẑ, padded with zeros — padding rows are filtered anyway) rides along as
    the 12th element; an optional ``host_zone`` i32 column (padded with
    zeros, same reasoning) plus the ``exclude_zone`` i32 scalar ride as the
    next two."""
    n, d = free_f.shape
    k = inst_cost.shape[1]
    pad = (-n) % tile
    free_f = jnp.asarray(free_f, jnp.float32)
    free_n = jnp.asarray(free_n, jnp.float32)
    sched = jnp.asarray(schedulable, jnp.float32)
    domain = jnp.asarray(domain, jnp.int32)
    slow = jnp.asarray(slow, jnp.float32)
    inst_res = jnp.asarray(inst_res, jnp.float32)
    inst_cost = jnp.asarray(inst_cost, jnp.float32)
    inst_valid = jnp.asarray(inst_valid, jnp.float32)
    if churn is not None:
        churn = jnp.asarray(churn, jnp.float32)
    if host_zone is not None:
        host_zone = jnp.asarray(host_zone, jnp.int32)
    if pad:
        zf = jnp.zeros((pad, d), jnp.float32)
        free_f = jnp.concatenate([free_f, zf])
        free_n = jnp.concatenate([free_n, zf])
        sched = jnp.concatenate([sched, jnp.zeros((pad,), jnp.float32)])
        domain = jnp.concatenate([domain, jnp.zeros((pad,), jnp.int32)])
        slow = jnp.concatenate([slow, jnp.ones((pad,), jnp.float32)])
        inst_res = jnp.concatenate([inst_res, jnp.zeros((pad, k, d), jnp.float32)])
        inst_cost = jnp.concatenate([inst_cost, jnp.zeros((pad, k), jnp.float32)])
        inst_valid = jnp.concatenate([inst_valid, jnp.zeros((pad, k), jnp.float32)])
        if churn is not None:
            churn = jnp.concatenate([churn, jnp.zeros((pad,), jnp.float32)])
        if host_zone is not None:
            host_zone = jnp.concatenate(
                [host_zone, jnp.zeros((pad,), jnp.int32)]
            )
    out = (
        free_f.T, free_n.T, sched[None, :], domain[None, :], slow[None, :],
        inst_res.transpose(1, 2, 0), inst_cost.T, inst_valid.T,
        jnp.asarray(req_res, jnp.float32).reshape(d, 1),
        jnp.asarray(req_preemptible, jnp.int32).reshape(1, 1),
        jnp.asarray(req_domain, jnp.int32).reshape(1, 1),
    )
    if churn is not None:
        out += (churn[None, :],)
    if host_zone is not None:
        out += (
            host_zone[None, :],
            jnp.asarray(exclude_zone, jnp.int32).reshape(1, 1),
        )
    return out


def sched_screen(
    free_f, free_n, schedulable, domain, slow,
    inst_res, inst_cost, inst_valid,
    req_res, req_preemptible, req_domain,
    weigher_multipliers,
    require_free_slot: bool,
    m_keep: int,
    interpret=None,
    tile: int = TILE_HOSTS,
    churn=None,
    churn_threshold=None,
    host_zone=None,
    exclude_zone=None,
):
    """Fused stage-1 screen.  Returns ``(top_scores, top_idx, consts)``:

      top_scores  (m_keep,) the m_keep best ``omega_ub`` values, descending,
                  ties by lowest host index (== ``lax.top_k`` order);
      top_idx     (m_keep,) their host indices.  Callers shortlist the first
                  m_keep-1 and use entry m_keep-1 as the admissibility
                  (u, j_u) witness — pass ``m_keep = M + 1``;
      consts      (10,) packed ``ScreenConsts`` for reconstructing the exact
                  per-candidate base terms / tolerances outside the kernel.

    Requires ``m_keep <= n_hosts`` (the caller's shortlist branch guarantees
    M < N).  Hosts are padded to the 128-lane tile with unschedulable
    entries, which can never outrank a real host.  ``churn`` (optional
    per-host ẑ column) and a static ``churn_threshold`` enable the
    failure-domain weigher term and hot-zone steering (see
    ``_tile_stage1``); with a 5th ``weigher_multipliers`` entry the churn
    normalization folds into consts slots 8/9.  ``host_zone`` (per-host
    zone-id i32 column) + ``exclude_zone`` (i32 scalar, negative = off)
    hard-filter the excluded zone for relocation re-placements.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if host_zone is None or exclude_zone is None:
        host_zone = exclude_zone = None
    n = free_f.shape[0]
    if not 1 <= m_keep <= n:
        raise ValueError(f"m_keep={m_keep} out of range for {n} hosts")
    s_buf = 1
    while s_buf < m_keep + tile:
        s_buf *= 2
    scores, idx, consts = _sched_screen_padded(
        _prep_inputs(
            free_f, free_n, schedulable, domain, slow,
            inst_res, inst_cost, inst_valid,
            req_res, req_preemptible, req_domain, tile, churn,
            host_zone, exclude_zone,
        ),
        multipliers=tuple(weigher_multipliers),
        require_free_slot=bool(require_free_slot),
        churn_threshold=(
            None if churn_threshold is None else float(churn_threshold)
        ),
        s_buf=s_buf,
        tile=tile,
        interpret=interpret,
    )
    return scores[0, :m_keep], idx[0, :m_keep], consts[0]


def sched_screen_consts(
    free_f, free_n, schedulable, domain, slow,
    inst_res, inst_cost, inst_valid,
    req_res, req_preemptible, req_domain,
    weigher_multipliers,
    require_free_slot: bool,
    interpret=None,
    tile: int = TILE_HOSTS,
    churn=None,
    churn_threshold=None,
    host_zone=None,
    exclude_zone=None,
):
    """Constants half of the split screen: fold ONLY the 10 normalization
    scalars over the given hosts (identical folds to ``sched_screen``'s
    phase 0).  Returns the packed (10,) ``ScreenConsts``.

    The sharded fused path (``jax_scheduler._sharded_screen`` with
    ``fused_screen=True``) runs this per shard, pmin/pmax-merges the
    results across the mesh, and feeds them to ``sched_screen_topm`` — the
    constants barrier the single-kernel 2-phase grid cannot cross."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if host_zone is None or exclude_zone is None:
        host_zone = exclude_zone = None
    consts = _sched_consts_padded(
        _prep_inputs(
            free_f, free_n, schedulable, domain, slow,
            inst_res, inst_cost, inst_valid,
            req_res, req_preemptible, req_domain, tile, churn,
            host_zone, exclude_zone,
        ),
        multipliers=tuple(weigher_multipliers),
        require_free_slot=bool(require_free_slot),
        churn_threshold=(
            None if churn_threshold is None else float(churn_threshold)
        ),
        tile=tile,
        interpret=interpret,
    )
    return consts[0]


def sched_screen_topm(
    free_f, free_n, schedulable, domain, slow,
    inst_res, inst_cost, inst_valid,
    req_res, req_preemptible, req_domain,
    consts,
    weigher_multipliers,
    require_free_slot: bool,
    m_keep: int,
    interpret=None,
    tile: int = TILE_HOSTS,
    churn=None,
    churn_threshold=None,
    host_zone=None,
    exclude_zone=None,
):
    """Top-M half of the split screen: score ``omega_ub`` against EXTERNAL
    packed constants (``consts``, e.g. mesh-merged) and fold the on-chip
    running top-``m_keep``.  Returns ``(top_scores, top_idx)`` with the
    same ordering contract as ``sched_screen``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if host_zone is None or exclude_zone is None:
        host_zone = exclude_zone = None
    n = free_f.shape[0]
    if not 1 <= m_keep <= n:
        raise ValueError(f"m_keep={m_keep} out of range for {n} hosts")
    s_buf = 1
    while s_buf < m_keep + tile:
        s_buf *= 2
    scores, idx = _sched_topm_padded(
        _prep_inputs(
            free_f, free_n, schedulable, domain, slow,
            inst_res, inst_cost, inst_valid,
            req_res, req_preemptible, req_domain, tile, churn,
            host_zone, exclude_zone,
        ),
        jnp.asarray(consts, jnp.float32).reshape(1, N_CONSTS),
        multipliers=tuple(weigher_multipliers),
        require_free_slot=bool(require_free_slot),
        churn_threshold=(
            None if churn_threshold is None else float(churn_threshold)
        ),
        s_buf=s_buf,
        tile=tile,
        interpret=interpret,
    )
    return scores[0, :m_keep], idx[0, :m_keep]
