"""Vectorized JAX implementation of the preemptible-aware scheduler.

The paper's single-pass design (Alg. 2+5+6) has a property the retry design
lacks: *the whole decision is a pure function of the host-state arrays* — no
data-dependent second cycle.  We exploit that to turn scheduling into one
jit-compiled array program over struct-of-arrays host state, organized as a
**two-stage shortlist-pruned pipeline**:

    stage 1 (O(N·K))  screen:    dual-view fit mask, exact feasibility
                                 (full-subset test), termination-cost bounds
                                 from the sorted per-slot costs, and an
                                 optimistic weigher score ``omega_ub``;
    stage 2 (O(M·2^K)) decide:   ``lax.top_k`` shortlist of M candidates,
                                 gather their (M, K, D) slot rows, exact
                                 Alg. 5 subset enumeration + exact weighing
                                 on the shortlist only.

Stage 1 itself has two executions sharing ONE definition of the bounds math
(``core.screen_math``): the pure-jnp assembly below (the oracle, and the CPU
default), and the fused Pallas kernel ``repro.kernels.sched_screen`` that
computes every screen term per 128-host tile and keeps the running top-M
resident on chip, emitting only the (M+1,) shortlist + 10 normalization
scalars — one pass over the fleet instead of a dozen HBM round-trips
(``fused_screen``: None = auto, on for TPU backends, interpret-capable
elsewhere; pinned bit-exact against the jnp screen by
tests/test_sched_screen.py).

Only the argmax host's termination plan is ever applied, so pruning is
*exact*: an admissibility check compares the winner's exact score against the
optimistic bound of every non-shortlisted host and falls back to the full
O(N·2^K) enumeration (``lax.cond``) in the rare case the shortlist could have
excluded the true winner.  Decisions are therefore bit-identical with the
unpruned path (pinned by tests/test_shortlist_parity.py), while the complexity
drops from O(N·2^K) to O(N·K + M·2^K) — K=12 (4096 masks) becomes affordable
at 10^5 hosts.

Cost functions must be *per-instance additive* (all of the paper's are:
period, count, revenue, recompute), so a subset's cost is ``mask @ inst_cost``
and Alg. 5 becomes a masked matmul + argmin — MXU-shaped work.  The Pallas
kernel in ``repro.kernels.sched_weigh`` fuses the stage-2 enumeration over
VMEM tiles (both the full fleet and the gathered shortlist); this module
provides the pure-jnp equivalent (also the kernel's oracle) and the
end-to-end scheduler wrapper used by benchmarks.

Capacity model: each host carries up to ``K`` preemptible instances (padded,
masked).  2^K subset masks are enumerated exactly — K≤12 covers every
practical oversubscription level (the paper's testbed peaked at 4).

Two state flavors:

* ``SoAHostState`` + ``build_soa_state`` — rebuilt from python ``Host``
  objects per call (the correctness oracle; O(N·K) python work per request);
* ``SoAFleetState`` + ``build_fleet_state`` — built once, then updated
  incrementally on device via the pure transitions below (``schedule_step``,
  ``schedule_many``, ``apply_*``) — the fleet-scale fast path driven by
  ``core.soa_fleet.SoAFleet`` / ``core.simulator.SoASimulator``.  The
  decision/transition entry points donate the input state's buffers
  (``donate_argnums``) so per-event updates happen in place; pass
  ``donate=False`` when the caller needs the input state afterwards.

Exactness note: with integer-valued resources and slot costs (the paper's
workload regime, and what every parity test generates) all the screen's sums
are exact in f32, its bounds hold bitwise, and shortlist decisions are
unconditionally identical to the full enumeration.  With arbitrary float
costs (e.g. the "revenue" kind's ``/period``), the bound sums can differ
from the enumeration's subset sums by f32 reassociation ulps; the
admissibility check pads its strict branch by that margin, leaving one
residual caveat: two hosts whose *exact* scores collide to the same f32
omega may resolve their tie differently between the two paths.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .cost import (
    BILL_PERIOD_S,
    CostFunction,
    CountCost,
    MixedCost,
    PeriodCost,
    RecomputeCost,
    RevenueCost,
)
from .policy import (
    COST_KIND_IDS,
    DEFAULT_SHORTLIST,
    SchedulerPolicy,
    ensure_policy,
)
from .screen_math import (
    EPS,
    NEG_INF,
    POS_INF,
    TIE_EPS,
    ScreenConsts,
    base_from_consts,
    churn_of,
    consts_of,
    floor_mod,
    inv_span,
    omega_of,
    oem_pairs as _oem_pairs,  # noqa: F401  (back-compat re-export)
    raw_base_terms,
    screen_bounds_rows,
    slot_cost_by_kind,
    sort_rows as _net_sort_cols,  # noqa: F401  (back-compat re-export)
    total_rows,
)
from .types import (
    EMPTY_PLAN,
    Host,
    Instance,
    Request,
    ScheduleResult,
    TerminationPlan,
)

# DEFAULT_SHORTLIST (the shortlist=None auto size; fleets not meaningfully
# larger keep the single-stage full enumeration) lives in ``policy`` and is
# re-exported here for back-compat.


# ---------------------------------------------------------------------------
# SoA host state
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SoAHostState:
    """Struct-of-arrays mirror of a host fleet (device-resident)."""

    free_f: jax.Array       # (N, D) h_f free resources
    free_n: jax.Array       # (N, D) h_n free resources
    schedulable: jax.Array  # (N,)   bool
    domain: jax.Array       # (N,)   int32
    slow: jax.Array         # (N,)   float32 straggler factor
    inst_res: jax.Array     # (N, K, D) preemptible instance resources (padded)
    inst_cost: jax.Array    # (N, K)    per-instance termination cost
    inst_valid: jax.Array   # (N, K)    bool
    #: optional per-host learned zone-churn rate ẑ (None = churn-blind;
    #: the persistent path derives it from the zone accumulators per step,
    #: the rebuild oracle freezes it at build via ``zone_rates``).
    churn: Optional[jax.Array] = None  # (N,) float32
    #: optional per-host zone id (None = zone-blind); consumed by the
    #: relocation plane's per-request zone-exclusion filter.
    host_zone: Optional[jax.Array] = None  # (N,) int32

    @property
    def n_hosts(self) -> int:
        return self.free_f.shape[0]

    @property
    def k_slots(self) -> int:
        return self.inst_res.shape[1]


def _hosts_to_arrays(
    hosts: Sequence[Host],
    k_slots: int,
    domain_ids: Optional[Dict[str, int]],
):
    """Shared host→array conversion for both state flavors: the common
    per-host columns plus the per-host preemptible lists (sorted by id),
    with the ``k_slots`` overflow check applied.

    Returns ``(d, free_f, free_n, schedulable, domain, slow, pre_lists)``.
    """
    n = len(hosts)
    d = len(hosts[0].capacity.spec.dims) if hosts else 0
    if domain_ids is None:
        domain_ids = {}
        for h in hosts:
            domain_ids.setdefault(h.domain, len(domain_ids))
    free_f = np.zeros((n, d), np.float32)
    free_n = np.zeros((n, d), np.float32)
    schedulable = np.zeros((n,), bool)
    domain = np.zeros((n,), np.int32)
    slow = np.ones((n,), np.float32)
    pre_lists: List[List[Instance]] = []
    for i, h in enumerate(hosts):
        free_f[i] = h.free_full.vec
        free_n[i] = h.free_normal.vec
        schedulable[i] = h.schedulable
        domain[i] = domain_ids[h.domain]
        slow[i] = h.slow_factor
        pre = sorted(h.preemptible_instances(), key=lambda x: x.id)
        if len(pre) > k_slots:
            raise ValueError(
                f"host {h.name} has {len(pre)} preemptible instances > k_slots={k_slots}"
            )
        pre_lists.append(pre)
    return d, free_f, free_n, schedulable, domain, slow, pre_lists


def build_soa_state(
    hosts: Sequence[Host],
    now: float,
    cost_fn: Optional[CostFunction] = None,
    k_slots: int = 8,
    domain_ids: Optional[Dict[str, int]] = None,
    zone_rates: Optional[Dict[str, float]] = None,
    zone_ids: Optional[Dict[str, int]] = None,
) -> Tuple[SoAHostState, List[List[Instance]]]:
    """Convert python ``Host`` objects to device arrays.

    Returns the state plus the per-host preemptible instance lists (slot
    order), needed to translate a winning mask back into instance ids.

    ``zone_rates`` optionally freezes a per-zone churn rate ẑ (zone name →
    rate; missing zones read 0.0) into the state's ``churn`` column — the
    rebuild oracle's counterpart of the persistent path's online-learned
    zone accumulators.  ``zone_ids`` (zone name → id; missing zones map to
    -2, which no exclusion operand ever matches) builds the ``host_zone``
    column the relocation plane's zone-exclusion filter reads.
    """
    cost_fn = cost_fn or PeriodCost()
    n = len(hosts)
    d, free_f, free_n, schedulable, domain, slow, pre_lists = _hosts_to_arrays(
        hosts, k_slots, domain_ids
    )
    inst_res = np.zeros((n, k_slots, d), np.float32)
    inst_cost = np.zeros((n, k_slots), np.float32)
    inst_valid = np.zeros((n, k_slots), bool)
    slots: List[List[Instance]] = []
    for i, pre in enumerate(pre_lists):
        slots.append(pre)
        for k, inst in enumerate(pre):
            inst_res[i, k] = inst.resources.vec
            inst_cost[i, k] = cost_fn.cost([inst], now)
            inst_valid[i, k] = True
    churn = None
    if zone_rates is not None:
        churn = jnp.asarray(
            [float(zone_rates.get(h.zone, 0.0)) for h in hosts], jnp.float32
        )
    host_zone = None
    if zone_ids is not None:
        host_zone = jnp.asarray(
            [int(zone_ids.get(h.zone, -2)) for h in hosts], jnp.int32
        )
    state = SoAHostState(
        free_f=jnp.asarray(free_f),
        free_n=jnp.asarray(free_n),
        schedulable=jnp.asarray(schedulable),
        domain=jnp.asarray(domain),
        slow=jnp.asarray(slow),
        inst_res=jnp.asarray(inst_res),
        inst_cost=jnp.asarray(inst_cost),
        inst_valid=jnp.asarray(inst_valid),
        churn=churn,
        host_zone=host_zone,
    )
    return state, slots


def subset_masks(k: int) -> np.ndarray:
    """(2^k, k) 0/1 matrix enumerating all subsets (row 0 = empty set)."""
    m = np.arange(1 << k, dtype=np.uint32)
    return ((m[:, None] >> np.arange(k)[None, :]) & 1).astype(np.float32)


def _masks_const(k: int) -> jax.Array:
    """The (2^k, k) mask matrix as a trace-time constant.

    Built from the *static* slot count inside jit, so it is folded into the
    compiled executable once instead of being transferred per call."""
    return jnp.asarray(subset_masks(k))


# ---------------------------------------------------------------------------
# The jit'd decision (pure jnp; also the Pallas kernel's oracle)
# ---------------------------------------------------------------------------


def host_plan_terms(
    free_f: jax.Array,
    inst_res: jax.Array,
    inst_cost: jax.Array,
    inst_valid: jax.Array,
    req_res: jax.Array,
    masks: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-host Alg. 5 terms, vectorized over all hosts and all 2^K masks.

    Returns (best_cost, best_mask_idx, any_feasible):
      best_cost   (N,)  cost of the cheapest feasible termination subset
                        (0 where the request already fits h_f),
      best_mask   (N,)  int32 index into ``masks``,
      feasible    (N,)  whether ANY subset admits the request.
    """
    # Invalid slots contribute nothing and cost +inf if ever selected.
    res = jnp.where(inst_valid[..., None], inst_res, 0.0)            # (N,K,D)
    cost = jnp.where(inst_valid, inst_cost, POS_INF)                 # (N,K)
    # One (N,K)@(K,M) matmul per resource dimension (D small, static →
    # unrolled) instead of materializing the (N,M,D) freed tensor — the same
    # MXU-shaped formulation as the Pallas kernel, and ~1.5x faster on CPU.
    mT = masks.T                                                     # (K,M)
    ok = None
    for d in range(res.shape[-1]):
        cond = free_f[:, d][:, None] + res[:, :, d] @ mT >= req_res[d] - EPS
        ok = cond if ok is None else (ok & cond)                     # (N,M)
    # Subsets touching an invalid slot are excluded via +inf cost.
    sub_cost = jnp.where(ok, cost @ mT, POS_INF)                     # (N,M)
    # Tie-break: cheaper cost first, then fewer instances, then first index
    # (matches the python reference).  Two-stage to stay exact in f32.
    best_cost = jnp.min(sub_cost, axis=-1)                           # (N,)
    size = masks.sum(-1)                                             # (M,)
    is_tie = sub_cost <= best_cost[:, None] + TIE_EPS
    size_key = jnp.where(is_tie, size[None, :], POS_INF)
    best_mask = jnp.argmin(size_key, axis=-1).astype(jnp.int32)      # (N,)
    feasible = jnp.any(ok, axis=-1)
    return best_cost, best_mask, feasible


def screen_terms(
    free_f: jax.Array,
    inst_res: jax.Array,
    inst_cost: jax.Array,
    inst_valid: jax.Array,
    req_res: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Stage-1 per-host screening terms, O(N·K) — no subset enumeration.

    Thin row-major adapter over ``screen_math.screen_bounds_rows`` (ONE
    shared definition with the fused Pallas screen): slices the (N, K, ...)
    slot arrays into slot-major rows so the Batcher compare-exchange network
    runs on contiguous host-vectors, which is also ~15% faster on XLA CPU
    than the previous host-major column slices.

    Returns ``(feasible, overcommitted, cost_lb, cost_ub)``, all (N,) —
    see ``screen_bounds_rows`` for the exact semantics of each term.
    """
    k = inst_res.shape[1]
    need = (req_res[None, :] - free_f).T                             # (D,N)
    res_rows = [
        jnp.where(inst_valid[:, i, None], inst_res[:, i, :], 0.0).T
        for i in range(k)
    ]
    cost_rows = [
        jnp.where(inst_valid[:, i], inst_cost[:, i], POS_INF) for i in range(k)
    ]
    total = total_rows(
        [jnp.where(inst_valid[:, i], inst_cost[:, i], 0.0) for i in range(k)]
    )
    return screen_bounds_rows(need, res_rows, cost_rows, total)


def _stage1_rows(
    free_f: jax.Array,
    free_n: jax.Array,
    schedulable: jax.Array,
    domain: jax.Array,
    slow: jax.Array,
    inst_res: jax.Array,
    inst_cost: jax.Array,
    inst_valid: jax.Array,
    req_res: jax.Array,
    req_preemptible: jax.Array,
    req_domain: jax.Array,
    require_free_slot: bool,
    churn: Optional[jax.Array] = None,
    churn_threshold: Optional[float] = None,
    host_zone: Optional[jax.Array] = None,
    exclude_zone: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, Tuple[jax.Array, ...]]:
    """Stage-1 screen assembly on row-major host arrays: the dual-view fit
    mask (the paper's trick), the shared ``screen_math`` bounds, and the raw
    enumeration-free weigher terms.

    ONE definition executed for the full fleet (jnp screen / fallback), for
    gathered candidate rows (the fused path's per-candidate recompute), and
    per shard under ``shard_map`` (the device-sharded screen) — all three
    see identical elementwise outputs, which is what keeps every stage-1
    backend bit-exact with the others.

    ``churn`` (per-host learned zone-churn rate ẑ, see ``churn_of``) adds
    the churn-penalty raw term; a static ``churn_threshold`` additionally
    steers preemptible placements off hot zones entirely (the graceful-
    degradation hard filter — normal requests are unaffected).

    ``host_zone`` + ``exclude_zone`` (the relocation plane's per-request
    operand, -1 = none) hard-filter an entire failure zone out of the
    screen — pure integer/boolean math, so the gate is trivially identical
    on every backend (the same shape of filter as ``req_domain``).

    Returns ``(valid, cost_lb, cost_ub, raw)`` (``raw`` grows a 4th entry
    when churn-aware).
    """
    view = jnp.where(req_preemptible, free_f, free_n)
    fits = jnp.all(view >= req_res[None, :] - EPS, axis=-1)
    fits &= schedulable
    fits &= (req_domain < 0) | (domain == req_domain)
    if exclude_zone is not None and host_zone is not None:
        # Relocation re-placements flee their source zone: no host of that
        # zone may win, regardless of how calm its churn currently reads.
        fits &= (exclude_zone < 0) | (host_zone != exclude_zone)
    if churn_threshold is not None and churn is not None:
        # Hot-zone steering: preemptible work avoids zones whose learned
        # churn rate crossed the policy threshold (normal work still lands —
        # its instances are not the ones zone churn kills).
        fits &= jnp.where(
            req_preemptible, churn <= jnp.float32(churn_threshold), True
        )
    if require_free_slot:
        # Persistent state carries K slots per host: a preemptible request
        # needs an empty slot (the rebuild path raises on overflow instead).
        fits &= jnp.where(req_preemptible, jnp.any(~inst_valid, axis=-1), True)
    feas, overcommitted, cost_lb, cost_ub = screen_terms(
        free_f, inst_res, inst_cost, inst_valid, req_res
    )
    # Preemptible requests never terminate others: zero cost everywhere.
    cost_lb = jnp.where(req_preemptible, 0.0, cost_lb)
    cost_ub = jnp.where(req_preemptible, 0.0, cost_ub)
    feas = jnp.where(req_preemptible, fits, feas)
    valid = fits & feas
    raw = raw_base_terms(jnp.sum(free_f, axis=-1), slow, overcommitted, churn)
    return valid, cost_lb, cost_ub, raw


def _base_of(mult, raw, consts: ScreenConsts, gates=None) -> jax.Array:
    """``base_from_consts`` over a 3- or 4-entry ``raw`` tuple (the 4th is
    the churn term) — the one unpacking every assembly site shares.
    ``gates`` = the static multipliers when ``mult`` carries traced
    per-lane values (ensemble axis); None gates on ``mult`` itself."""
    churn_raw = raw[3] if len(raw) > 3 else None
    return base_from_consts(
        mult, raw[0], raw[1], raw[2], consts, churn_raw=churn_raw,
        gates=gates,
    )


def _sharded_screen(
    mesh,
    free_f, free_n, schedulable, domain, slow,
    inst_res, inst_cost, inst_valid,
    req_res, req_preemptible, req_domain,
    mult: Tuple[float, ...],
    require_free_slot: bool,
    m_cand: int,
    use_fused: bool = False,
    churn: Optional[jax.Array] = None,
    churn_threshold: Optional[float] = None,
    host_zone: Optional[jax.Array] = None,
    exclude_zone: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Stage-1 screen per host-major shard under ``jax.shard_map``.

    Each shard runs the unchanged ``screen_math`` bounds on its block of
    hosts, folds its local normalization partials, and the mesh merges:

      * ``ScreenConsts`` via ``lax.pmin``/``lax.pmax`` — min/max are
        reassociation-free, so the merged scalars are bitwise equal to the
        unsharded fleet-wide folds in ``consts_of``;
      * a per-shard top-M (``lax.top_k`` — kept at M so XLA CPU's fast TopK
        custom-call still applies per shard) plus the shard's own
        admissibility witness (masked argmax, ties to the lowest index),
        tagged with GLOBAL host indices and ``all_gather``-ed.

    Returns replicated ``(scores (S·(M+1),), idxs (S·(M+1),), consts (10,))``
    for ``fleet_sharding.merge_shortlists`` to reduce into the global
    shortlist.  Callers guarantee ``N % S == 0`` and ``N/S ≥ m_cand + 1``.
    ``churn`` (optional per-host ẑ, sharded host-major like the other rows)
    and a static ``churn_threshold`` thread the failure-domain terms through
    the per-shard screen — the merged churn-normalization scalars come out
    of the same pmin/pmax folds, so churn-aware sharded decisions stay
    bit-exact with the unsharded screen.  ``host_zone`` (sharded host-major)
    + ``exclude_zone`` (replicated scalar) thread the relocation plane's
    zone-exclusion filter the same way — a pure boolean row gate, so
    sharding cannot perturb it.

    ``use_fused`` runs the shard-local screen through the fused Pallas
    kernel instead of the jnp assembly, split at the constants barrier
    (``sched_screen_consts`` → pmin/pmax merge → ``sched_screen_topm``): the
    per-shard top-(M+1) then comes out of the kernel's on-chip bitonic fold,
    computed from the SAME merged constants the jnp shards use, so the
    forwarded (score, index) pairs are identical and the kernel and mesh
    stop being mutually exclusive.  (One benign exception: a shard whose
    non-shortlisted hosts are ALL invalid (score NEG_INF) may forward a
    different — equally inert — witness index than the jnp masked argmax;
    both are dominated by every real candidate and cannot change a
    decision.)  On non-TPU backends the kernel runs in interpret mode
    (parity-gated by tests/test_sharded_parity.py).
    """
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    m_term = mult[1]

    def shard_fn(free_f, free_n, schedulable, domain, slow,
                 inst_res, inst_cost, inst_valid,
                 req_res, req_preemptible, req_domain, *extras):
        # The optional failure-domain operands arrive positionally in a
        # fixed order (churn row, zone row, exclusion scalar) — decode by
        # which ones the caller actually supplied.
        extra = list(extras)
        churn_l = extra.pop(0) if churn is not None else None
        zone_l = extra.pop(0) if host_zone is not None else None
        excl_l = extra.pop(0) if exclude_zone is not None else None
        t = free_f.shape[0]  # hosts per shard
        offset = (jax.lax.axis_index(axis) * t).astype(jnp.int32)
        if use_fused:
            from repro.kernels.sched_screen import (
                sched_screen_consts,
                sched_screen_topm,
            )

            kern_args = (
                free_f, free_n, schedulable, domain, slow,
                inst_res, inst_cost, inst_valid,
                req_res, req_preemptible, req_domain,
            )
            local = ScreenConsts.unpack(sched_screen_consts(
                *kern_args,
                weigher_multipliers=mult,
                require_free_slot=require_free_slot,
                churn=churn_l,
                churn_threshold=churn_threshold,
                host_zone=zone_l,
                exclude_zone=excl_l,
            ))
        else:
            valid, cost_lb, cost_ub, raw = _stage1_rows(
                free_f, free_n, schedulable, domain, slow,
                inst_res, inst_cost, inst_valid,
                req_res, req_preemptible, req_domain, require_free_slot,
                churn=churn_l, churn_threshold=churn_threshold,
                host_zone=zone_l, exclude_zone=excl_l,
            )
            local = consts_of(mult, valid, cost_lb, cost_ub, *raw)
        consts = ScreenConsts(
            jax.lax.pmin(local.c_lo, axis), jax.lax.pmax(local.c_hi, axis),
            jax.lax.pmin(local.over_lo, axis), jax.lax.pmax(local.over_hi, axis),
            jax.lax.pmin(local.pack_lo, axis), jax.lax.pmax(local.pack_hi, axis),
            jax.lax.pmin(local.strag_lo, axis), jax.lax.pmax(local.strag_hi, axis),
            jax.lax.pmin(local.churn_lo, axis), jax.lax.pmax(local.churn_hi, axis),
        )
        if use_fused:
            # Kernel top-(M+1) from the MERGED constants; entry M is the
            # shard's admissibility witness (best non-shortlisted omega_ub,
            # lax.top_k tie order — the same candidate the masked argmax
            # surfaces whenever it is a real score).
            s_all, i_all = sched_screen_topm(
                *kern_args,
                consts=consts.pack(),
                weigher_multipliers=mult,
                require_free_slot=require_free_slot,
                m_keep=m_cand + 1,
                churn=churn_l,
                churn_threshold=churn_threshold,
                host_zone=zone_l,
                exclude_zone=excl_l,
            )
            scores = s_all
            idxs = i_all.astype(jnp.int32) + offset
        else:
            base = _base_of(mult, raw, consts)
            ispan_ub = inv_span(consts.c_lo, consts.c_hi)
            opt_cost = cost_lb if m_term >= 0 else cost_ub
            omega_ub = omega_of(opt_cost, base, valid, consts, ispan_ub, m_term)
            s_loc, p_loc = jax.lax.top_k(omega_ub, m_cand)
            in_short = jnp.zeros((t,), bool).at[p_loc].set(True)
            out_ub = jnp.where(in_short, jnp.float32(NEG_INF), omega_ub)
            u_loc = jnp.max(out_ub)
            ju_loc = jnp.argmax(out_ub).astype(jnp.int32) + offset
            scores = jnp.concatenate([s_loc, u_loc[None]])
            idxs = jnp.concatenate(
                [p_loc.astype(jnp.int32) + offset, ju_loc[None]]
            )
        all_s = jax.lax.all_gather(scores, axis).reshape(-1)
        all_i = jax.lax.all_gather(idxs, axis).reshape(-1)
        return all_s, all_i, consts.pack()

    row = P(axis)
    rep = P()
    operands = (
        free_f, free_n, schedulable, domain, slow,
        inst_res, inst_cost, inst_valid,
        req_res, req_preemptible, req_domain,
    )
    in_specs = (row,) * 8 + (rep, rep, rep)
    if churn is not None:
        # The churn column shards host-major like every other per-host row.
        operands += (churn,)
        in_specs += (row,)
    if host_zone is not None:
        operands += (host_zone,)
        in_specs += (row,)
    if exclude_zone is not None:
        # The per-request exclusion id is a replicated scalar (like req_*).
        operands += (exclude_zone,)
        in_specs += (rep,)
    return jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(rep, rep, rep),
        check_vma=False,
    )(*operands)


def _plan_terms(use_pallas: bool, gathered: bool = False):
    """Enumeration backend: Pallas kernel (full-fleet or gathered-shortlist
    tiling) or the pure-jnp oracle."""
    if use_pallas:
        from repro.kernels.sched_weigh import sched_weigh, sched_weigh_gathered

        return sched_weigh_gathered if gathered else sched_weigh
    return host_plan_terms


def _decision_core(
    free_f: jax.Array,
    free_n: jax.Array,
    schedulable: jax.Array,
    domain: jax.Array,
    slow: jax.Array,
    inst_res: jax.Array,
    inst_cost: jax.Array,
    inst_valid: jax.Array,
    req_res: jax.Array,
    req_preemptible: jax.Array,
    req_domain: jax.Array,
    policy: SchedulerPolicy,
    require_free_slot: bool,
    churn: Optional[jax.Array] = None,
    host_zone: Optional[jax.Array] = None,
    exclude_zone: Optional[jax.Array] = None,
    mult_val: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """The two-stage decision pipeline on raw SoA arrays (shared by the
    rebuild path, the persistent fast path, and the batched ``lax.scan``
    path).  ``policy`` is the ONE static knob bundle (``core.policy``); the
    fields it reads here:

    ``policy.shortlist``: stage-2 candidate count M.  ``None`` = auto (64 at
    fleet scale, full enumeration for small fleets); ``0`` disables pruning.
    Any value yields decisions bit-identical to the full enumeration — when
    the admissibility check cannot certify the shortlist, the full path runs
    via ``lax.cond``.

    ``policy.fused_screen``: run stage 1 through the fused Pallas kernel
    (``repro.kernels.sched_screen``) instead of the jnp assembly.  ``None``
    = auto (on for TPU backends, where it collapses the screen's HBM
    round-trips into one pass; off elsewhere — the kernel stays available in
    interpret mode for parity testing).  Both screens execute the shared
    ``screen_math`` definitions, so the decision is identical either way.

    ``policy.mesh``: a 1-D ``jax.sharding.Mesh`` (see ``fleet_sharding``)
    running stage 1 per host-major shard under ``shard_map`` with a bit-exact
    cross-shard merge — the fleet-scale path past the single-device ceiling.
    Combined with ``fused_screen=True`` the kernel runs *per shard* inside
    ``shard_map`` (split at the constants barrier).  Requires the host
    count to divide across the mesh with ≥ M+1 hosts per shard (pad with
    ``fleet_sharding.padded_hosts``/``pad_fleet_state``); otherwise the
    unsharded screen runs (same decision, just not shard-parallel).

    ``policy.use_pallas`` selects the stage-2 enumeration backend;
    ``policy.weigher_multipliers`` the scoring policy.  The slot costs in
    ``inst_cost`` are computed by the caller (``fleet_slot_costs`` for
    persistent states — including the heterogeneous kind-table selection —
    or frozen at build for ``SoAHostState``), so every screen backend
    consumes identical cost arrays.

    Returns ``(host_idx, term_mask_idx, ok, fell_back, margin)``:
    ``fell_back`` flags decisions where the admissibility check could not
    certify the shortlist and the full enumeration ran; ``margin`` is the
    admissibility headroom ``best_val - u`` (POS_INF when no valid host or
    pruning was off) — the signals the adaptive shortlist controller
    (``soa_fleet.AdaptiveShortlist``) steers M with.
    """
    use_pallas = policy.use_pallas
    mesh = policy.mesh
    shortlist = policy.shortlist
    fused_screen = policy.fused_screen
    n_hosts, k = inst_res.shape[0], inst_res.shape[1]
    masks = _masks_const(k)
    if shortlist is None:
        shortlist = DEFAULT_SHORTLIST if n_hosts > 4 * DEFAULT_SHORTLIST else 0
    m_cand = min(int(shortlist), n_hosts)
    if fused_screen is None:
        fused_screen = jax.default_backend() == "tpu" and mesh is None
    # Failure-domain plane: churn-aware only when the caller supplied the ẑ
    # column AND the policy turns a churn knob — otherwise both are dropped
    # statically and the compiled program is the exact churn-blind one.
    churn_on = churn is not None and policy.churn_aware
    if not churn_on:
        churn = None
    # Relocation plane: the zone-exclusion operand rides only when the
    # caller supplied the zone column AND the policy turns the plane on —
    # relocation-off policies compile the exact pre-relocation program.
    zone_on = (
        host_zone is not None
        and exclude_zone is not None
        and policy.relocation_on
    )
    if not zone_on:
        host_zone = None
        exclude_zone = None
    mult = policy.all_multipliers if churn_on else policy.weigher_multipliers
    thr = policy.churn_threshold if churn_on else None
    # Ensemble multiplier axis: ``mult_val`` carries traced per-lane weigher
    # values (a (5,) f32 vector under vmap); the STATIC policy multipliers
    # keep their role as compile-time term gates (``gates``), so lanes share
    # one program whose included terms — and the termination-cost bound side
    # (`opt_cost`) — are fixed by the policy while the arithmetic rides the
    # lane values.  ``mult_val=None`` (every pre-existing caller) compiles
    # the exact unchanged program.
    gates = mult
    if mult_val is not None:
        mult = tuple(mult_val[i] for i in range(len(gates)))
    m_term = mult[1]
    m_term_gate = gates[1]
    use_mesh = (
        mesh is not None
        and m_cand > 0
        and n_hosts % mesh.size == 0
        and n_hosts // mesh.size >= m_cand + 1
    )
    if mult_val is not None and (use_mesh or fused_screen):
        raise NotImplementedError(
            "traced multiplier values (ensemble axis) are not supported on "
            "the mesh/fused-screen stage-1 paths — those close the static "
            "multipliers over shard_map / the Pallas kernel; run the "
            "ensemble with fused_screen=False and mesh=None"
        )

    def stage1_of(free_f, free_n, schedulable, domain, slow, inst_res,
                  inst_cost, inst_valid, churn=None, host_zone=None):
        """Stage-1 screen assembly on row-major arrays (the shared
        ``_stage1_rows`` with this decision's request closed over) — used
        for the full fleet (jnp screen / fallback) and for gathered
        candidate rows (the fused/sharded paths' per-candidate recompute).
        Same shared math as the kernel and the sharded screen, so the
        outputs agree elementwise.  ``exclude_zone`` (a replicated scalar,
        like the request operands) is closed over."""
        return _stage1_rows(
            free_f, free_n, schedulable, domain, slow,
            inst_res, inst_cost, inst_valid,
            req_res, req_preemptible, req_domain, require_free_slot,
            churn=churn, churn_threshold=thr,
            host_zone=host_zone, exclude_zone=exclude_zone,
        )

    def full_decision(_):
        """Single-stage path: exact enumeration over every host.  Fully
        self-contained (the fused screen never materializes fleet-wide
        terms, so the fallback recomputes stage 1 with the same shared math
        — bit-identical to the ``shortlist=0`` result either way)."""
        valid, cost_lb, cost_ub, raw = stage1_of(
            free_f, free_n, schedulable, domain, slow,
            inst_res, inst_cost, inst_valid, churn, host_zone,
        )
        consts = consts_of(gates, valid, cost_lb, cost_ub, *raw)
        base = _base_of(mult, raw, consts, gates=gates)
        ispan = inv_span(consts.c_lo, consts.c_hi)
        best_cost, best_mask, _ = _plan_terms(use_pallas)(
            free_f, inst_res, inst_cost, inst_valid, req_res, masks
        )
        best_cost = jnp.where(req_preemptible, 0.0, best_cost)
        best_mask = jnp.where(req_preemptible, 0, best_mask)
        omega = omega_of(best_cost, base, valid, consts, ispan, m_term,
                         gate=m_term_gate)
        host_idx = jnp.argmax(omega).astype(jnp.int32)
        return host_idx, best_mask[host_idx], omega[host_idx] > NEG_INF / 2

    if m_cand <= 0 or m_cand >= n_hosts:
        h, bm, ok = full_decision(None)
        return h, bm, ok, jnp.asarray(False), jnp.float32(POS_INF)

    # ---- stage 1: O(N·K) screen → top-M candidates + (u, j_u) witness -------
    # omega_ub ≥ omega at float level: cost_lb ≤ best_cost and every op in
    # omega_of is monotone (shared constants, shared add order).
    with jax.named_scope("stage1"):
        if use_mesh:
            # Per-shard screen under shard_map; the merge reduces the
            # gathered per-shard (top-M + witness) pairs into the global
            # shortlist with lax.top_k's exact tie ordering, and the
            # pmin/pmax-merged constants are bitwise equal to the fleet-wide
            # folds.  fused_screen=True runs the per-shard screen through the
            # Pallas kernel (no longer mutually exclusive with the mesh).
            from .fleet_sharding import merge_shortlists

            all_s, all_i, consts_arr = _sharded_screen(
                mesh,
                free_f, free_n, schedulable, domain, slow,
                inst_res, inst_cost, inst_valid,
                req_res, req_preemptible, req_domain,
                mult, require_free_slot, m_cand,
                use_fused=bool(fused_screen),
                churn=churn, churn_threshold=thr,
                host_zone=host_zone, exclude_zone=exclude_zone,
            )
            consts = ScreenConsts.unpack(consts_arr)
            cand, u, j_u = merge_shortlists(all_s, all_i, m_cand)
            # Per-candidate base/valid recomputed on the gathered
            # (replicated) shortlist rows — elementwise identical to the
            # fleet-wide values.
            valid_c, _, _, raw_c = stage1_of(
                free_f[cand], free_n[cand], schedulable[cand], domain[cand],
                slow[cand], inst_res[cand], inst_cost[cand], inst_valid[cand],
                churn[cand] if churn_on else None,
                host_zone[cand] if zone_on else None,
            )
            base_c = _base_of(mult, raw_c, consts, gates=gates)
        elif fused_screen:
            # One fused pass over the fleet; only the (M+1,) shortlist and
            # the 10 normalization scalars come back.  Entry M is the best
            # omega_ub outside the shortlist with lax.top_k tie ordering —
            # the (u, j_u) admissibility witness.
            from repro.kernels.sched_screen import sched_screen

            top_s, top_i, consts_arr = sched_screen(
                free_f, free_n, schedulable, domain, slow,
                inst_res, inst_cost, inst_valid,
                req_res, req_preemptible, req_domain,
                weigher_multipliers=mult,
                require_free_slot=require_free_slot,
                m_keep=m_cand + 1,
                churn=churn,
                churn_threshold=thr,
                host_zone=host_zone,
                exclude_zone=exclude_zone,
            )
            consts = ScreenConsts.unpack(consts_arr)
            cand = top_i[:m_cand]
            u, j_u = top_s[m_cand], top_i[m_cand]
            # Per-candidate base/valid recomputed on the gathered rows from
            # the kernel's constants — elementwise identical to the
            # fleet-wide jnp values (min/max folds are reassociation-free).
            valid_c, _, _, raw_c = stage1_of(
                free_f[cand], free_n[cand], schedulable[cand], domain[cand],
                slow[cand], inst_res[cand], inst_cost[cand], inst_valid[cand],
                churn[cand] if churn_on else None,
                host_zone[cand] if zone_on else None,
            )
            base_c = _base_of(mult, raw_c, consts, gates=gates)
        else:
            valid, cost_lb, cost_ub, raw = stage1_of(
                free_f, free_n, schedulable, domain, slow,
                inst_res, inst_cost, inst_valid, churn, host_zone,
            )
            consts = consts_of(gates, valid, cost_lb, cost_ub, *raw)
            base = _base_of(mult, raw, consts, gates=gates)
            ispan_ub = inv_span(consts.c_lo, consts.c_hi)
            # Bound side chosen by the STATIC sign: ensemble lanes must keep
            # the policy's sign so omega_ub stays an upper bound (validated
            # by scan_sim.simulate_ensemble before any lane runs).
            opt_cost = cost_lb if m_term_gate >= 0 else cost_ub
            omega_ub = omega_of(opt_cost, base, valid, consts, ispan_ub,
                                m_term, gate=m_term_gate)
            # NOTE: top_k(M) + a masked argmax for the (u, j_u) witness, NOT
            # the seemingly cleaner top_k(M+1) whose entry M is the same
            # witness: XLA CPU only rewrites top_k into its fast TopK
            # custom-call for k ≤ 64, so with the default M=64 the +1 falls
            # off a cliff into a full stable sort of all N hosts (~22 ms at
            # N=65536 — measured).
            _, cand = jax.lax.top_k(omega_ub, m_cand)        # ties → low idx
            in_short = jnp.zeros((n_hosts,), bool).at[cand].set(True)
            out_ub = jnp.where(in_short, NEG_INF, omega_ub)
            u = jnp.max(out_ub)
            j_u = jnp.argmax(out_ub).astype(jnp.int32)
            valid_c, base_c = valid[cand], base[cand]

    # ---- stage 2: exact enumeration on the gathered shortlist ---------------
    with jax.named_scope("stage2"):
        ispan = inv_span(consts.c_lo, consts.c_hi)
        bc_s, bm_s, _ = _plan_terms(use_pallas, gathered=True)(
            free_f[cand], inst_res[cand], inst_cost[cand], inst_valid[cand],
            req_res, masks,
        )
        bc_s = jnp.where(req_preemptible, 0.0, bc_s)
        bm_s = jnp.where(req_preemptible, 0, bm_s)
        omega_s = omega_of(bc_s, base_c, valid_c, consts, ispan, m_term,
                           gate=m_term_gate)  # (M,)
        best_val = jnp.max(omega_s)
        # Winner = lowest ORIGINAL index among exact-score ties (what the
        # full path's argmax-first-hit does over the whole fleet).
        tie_idx = jnp.where(omega_s == best_val, cand, n_hosts)
        winner_pos = jnp.argmin(tie_idx).astype(jnp.int32)
        w_star = tie_idx[winner_pos].astype(jnp.int32)
        ok_s = best_val > NEG_INF / 2

        # ---- admissibility: can any non-shortlisted host still win? ---------
        # An outside host beats w* only with omega > best_val, or omega ==
        # best_val and a lower index; its omega_ub caps both.  ~ok_s ⇒ no
        # valid host exists anywhere (the top-M would have surfaced one), so
        # the shortlist result (host 0, ok=False) already matches the full
        # path.
        #
        # With integer-valued costs (the paper regime; all sums are exact in
        # f32) ``cost_lb ≤ best_cost`` holds bitwise and ``u < best_val`` is
        # already safe.  With arbitrary float costs the bound's ≤K-term sums
        # may overshoot the enumeration's subset sums by a few ulp of
        # reassociation error, so pad the strict branch by that margin; the
        # exact-tie branch keeps the fast path for mass-tied fleets (see
        # module docstring for the residual ulp-tie caveat on non-integer
        # inputs).
        if m_term_gate:
            # python ``abs`` for the static program (constant-folded as
            # before); jnp.abs when the lane value is a tracer.
            m_abs = abs(m_term) if mult_val is None else jnp.abs(m_term)
            tol = m_abs * ispan * (3.0 * k * 1.2e-7) * jnp.maximum(
                jnp.abs(consts.c_hi), jnp.abs(consts.c_lo)
            )
        else:
            tol = 0.0
        admissible = (
            (u < best_val - tol) | ((u == best_val) & (j_u > w_star)) | ~ok_s
        )
        margin = jnp.where(ok_s, best_val - u, jnp.float32(POS_INF))

    @jax.named_scope("fallback")
    def fallback(_):
        return full_decision(None)

    h, bm, ok = jax.lax.cond(
        admissible,
        lambda _: (w_star, bm_s[winner_pos], ok_s),
        fallback,
        operand=None,
    )
    return h, bm, ok, ~admissible, margin


@functools.partial(jax.jit, static_argnames=("policy",))
def _decision_entry(
    state: SoAHostState,
    req_res: jax.Array,
    req_preemptible: jax.Array,
    req_domain: jax.Array,
    req_exclude_zone: jax.Array,
    *,
    policy: SchedulerPolicy,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    churn = state.churn
    if churn is None and policy.churn_aware:
        # Churn-aware policy over a state built without rates: all-zero ẑ
        # (every host equally calm — the weigher term normalizes away).
        churn = jnp.zeros_like(state.slow)
    host_zone = state.host_zone
    if host_zone is None and policy.relocation_on:
        # Relocation-capable policy over a state built without zone ids:
        # every host in zone 0 — an exclusion id of 0 then excludes the
        # whole fleet, anything else excludes nothing (and -1 = none).
        host_zone = jnp.zeros_like(state.domain)
    return _decision_core(
        state.free_f, state.free_n, state.schedulable, state.domain,
        state.slow, state.inst_res, state.inst_cost, state.inst_valid,
        req_res, req_preemptible, req_domain,
        policy, require_free_slot=False, churn=churn,
        host_zone=host_zone, exclude_zone=req_exclude_zone,
    )[:3]


def schedule_decision(
    state: SoAHostState,
    req_res: jax.Array,          # (D,)
    req_preemptible: jax.Array,  # () bool
    req_domain: jax.Array,       # () int32; -1 = any
    policy: Optional[SchedulerPolicy] = None,
    req_exclude_zone: jax.Array = -1,  # () int32 zone id; -1 = none
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One scheduling decision.  Returns (host_idx, term_mask_idx, ok).

    ``policy`` is the single static knob bundle (``SchedulerPolicy``):
    ``weigher_multipliers`` = (overcommit, termination_cost, packing,
    straggler) — the first two reproduce the paper's evaluation policy;
    ``shortlist`` = stage-2 candidate count (None = auto, 0 = off);
    ``fused_screen`` = stage-1 backend (None = auto: fused Pallas screen on
    TPU, jnp elsewhere); ``mesh`` = optional 1-D device mesh sharding
    stage 1 host-major (see ``fleet_sharding``); any setting returns the
    same decision (see ``_decision_core``).  Equal policies hit one jit
    cache entry.
    """
    policy = ensure_policy(policy, "schedule_decision")
    return _decision_entry(
        state, req_res, req_preemptible, req_domain,
        jnp.asarray(req_exclude_zone, jnp.int32), policy=policy,
    )


# ---------------------------------------------------------------------------
# Persistent device-resident fleet state + incremental transitions
# ---------------------------------------------------------------------------
#
# ``build_soa_state`` rebuilds every array from python ``Host`` objects on
# every call — O(N·K) python work that dominates latency at fleet scale.  The
# persistent view below is built ONCE and then mutated purely on device:
# termination costs are derived from per-slot start times at decision time
# (so the state never goes stale), placements allocate a free slot, and a
# ``lax.scan`` runs whole request batches with each decision seeing the
# previous ones' placements.  The rebuild path stays as the correctness
# oracle (see tests/test_soa_incremental.py).


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SoAFleetState:
    """Persistent struct-of-arrays fleet view (device-resident).

    Unlike ``SoAHostState`` (whose ``inst_cost`` is frozen at build time),
    slots carry ``inst_start``/``inst_price``/``inst_ckpt`` so the
    termination cost is a pure function of (state, now) — the prerequisite
    for incremental reuse.
    """

    free_f: jax.Array       # (N, D) h_f free resources
    free_n: jax.Array       # (N, D) h_n free resources
    schedulable: jax.Array  # (N,)   bool
    domain: jax.Array       # (N,)   int32
    slow: jax.Array         # (N,)   float32 straggler factor
    inst_res: jax.Array     # (N, K, D) preemptible slot resources (padded)
    inst_start: jax.Array   # (N, K)    slot start times
    inst_price: jax.Array   # (N, K)    slot price rates
    inst_ckpt: jax.Array    # (N, K)    last durable-checkpoint times
    inst_cost_kind: jax.Array  # (N, K) int32 billing-kind id (COST_KIND_IDS;
                               #        -1 = the policy's default kind)
    inst_period: jax.Array  # (N, K) per-slot billing period (s) for the
                            #        period/revenue kinds; -1 = policy default
    inst_valid: jax.Array   # (N, K)    bool
    #: Failure-domain plane: each host belongs to one zone (cloud AZ / rack),
    #: and the involuntary-termination (T) and accumulated-uptime (U)
    #: counters are tracked PER ZONE, updated in place by the transitions
    #: below.  The learned zone churn rate ẑ = T / max(U, ε) feeds the
    #: churn-penalty weigher and the hot-zone steering filter
    #: (``SchedulerPolicy.churn_multiplier`` / ``churn_threshold``).
    host_zone: jax.Array    # (N,)   int32 zone id
    zone_term: jax.Array    # (Z,)   float32 involuntary terminations (T)
    zone_up: jax.Array      # (Z,)   float32 accumulated uptime seconds (U)

    @property
    def n_hosts(self) -> int:
        return self.free_f.shape[0]

    @property
    def k_slots(self) -> int:
        return self.inst_res.shape[1]

    @property
    def n_zones(self) -> int:
        return self.zone_term.shape[0]


def jax_cost_params(cost_fn: CostFunction) -> Tuple[str, float]:
    """Map a python cost module onto the jnp slot-cost kinds.

    Returns ``(kind, period_s)``.  Only per-instance additive costs that are
    pure functions of (start_time, price, last_checkpoint, resources, now)
    are expressible on device; anything else must use the rebuild path
    (``build_soa_state``).
    """
    if isinstance(cost_fn, PeriodCost):
        return "period", cost_fn.period_s
    if isinstance(cost_fn, CountCost):
        return "count", BILL_PERIOD_S
    if isinstance(cost_fn, RevenueCost):
        return "revenue", cost_fn.period_s
    if isinstance(cost_fn, RecomputeCost):
        return "recompute", BILL_PERIOD_S
    if isinstance(cost_fn, MixedCost):
        raise ValueError(
            "MixedCost is a kind TABLE, not a single kind; build the policy "
            "with SchedulerPolicy.for_cost(cost_fn) instead"
        )
    raise ValueError(
        f"cost function {cost_fn.name!r} has no device-resident equivalent; "
        "use the rebuild path (build_soa_state + schedule_decision)"
    )


def slot_costs(
    cost_kind: str,
    inst_start: jax.Array,
    inst_price: jax.Array,
    now: jax.Array,
    period: jax.Array,
    inst_ckpt: Optional[jax.Array] = None,
    inst_res: Optional[jax.Array] = None,
) -> jax.Array:
    """Per-slot termination cost at time ``now`` (invalid slots are masked
    downstream, so garbage values on them are harmless).

    The period kinds use ``screen_math.floor_mod`` instead of ``%``: XLA
    CPU's fmod was the single most expensive op of the whole decision at
    10^5 hosts (~19 ms at N=65536·K=8 vs ~0.6 ms for the floor form, which
    is bit-identical on the integer-second workloads every parity test
    runs — see ``floor_mod`` for the boundary-correction argument)."""
    if cost_kind == "period":
        return floor_mod(now - inst_start, period)
    if cost_kind == "count":
        return jnp.ones_like(inst_start)
    if cost_kind == "revenue":
        return floor_mod(now - inst_start, period) / period * inst_price
    if cost_kind == "recompute":
        # Chip-seconds of work lost since the last durable checkpoint
        # (== core.cost.RecomputeCost; dim 0 is chips/vcpus by convention).
        lost = jnp.maximum(0.0, now - inst_ckpt)
        return lost * jnp.maximum(1.0, inst_res[..., 0])
    raise ValueError(f"unknown cost kind {cost_kind!r}")


def mixed_slot_costs(
    policy: SchedulerPolicy,
    inst_cost_kind: jax.Array,
    inst_start: jax.Array,
    inst_price: jax.Array,
    inst_ckpt: jax.Array,
    inst_res: jax.Array,
    now: jax.Array,
    inst_period: Optional[jax.Array] = None,
) -> jax.Array:
    """Heterogeneous per-slot termination cost: each slot billed by ITS OWN
    kind (``inst_cost_kind``; -1 = the policy default) through the branchless
    ``screen_math.slot_cost_by_kind`` select.  Every branch is the verbatim
    single-kind formula, so slot values are bit-identical to the homogeneous
    paths kind-for-kind (the device half of the ``cost.MixedCost`` oracle).
    ``inst_period`` (optional, -1 sentinel = policy default) carries per-slot
    contract periods for the period/revenue kinds."""
    eff = jnp.where(
        inst_cost_kind >= 0, inst_cost_kind, jnp.int32(policy.default_kind_id)
    )
    period = jnp.float32(policy.period)
    if inst_period is not None:
        period = jnp.where(inst_period > 0, inst_period, period)
    return slot_cost_by_kind(
        eff, inst_start, inst_price, inst_ckpt, inst_res[..., 0],
        now, period,
    )


def fleet_slot_costs(
    state: "SoAFleetState", now: jax.Array, policy: SchedulerPolicy
) -> jax.Array:
    """Per-slot termination costs of a persistent fleet state under
    ``policy``'s cost table.  Single-kind policies compile the exact
    pre-policy program (the kind column is never read); mixed tables select
    per slot.  The ``inst_period`` column overrides the policy's shared
    billing period per slot (-1 sentinel = default); with every slot at the
    sentinel the select yields elementwise-identical values to the shared
    period, so homogeneous parity is bitwise."""
    period = jnp.where(
        state.inst_period > 0, state.inst_period, jnp.float32(policy.period)
    )
    if not policy.mixed:
        return slot_costs(
            policy.cost_kind, state.inst_start, state.inst_price, now,
            period, inst_ckpt=state.inst_ckpt, inst_res=state.inst_res,
        )
    return mixed_slot_costs(
        policy, state.inst_cost_kind, state.inst_start, state.inst_price,
        state.inst_ckpt, state.inst_res, now, inst_period=state.inst_period,
    )


def build_fleet_state(
    hosts: Sequence[Host],
    k_slots: int = 8,
    domain_ids: Optional[Dict[str, int]] = None,
    slot_assignment: Optional[Sequence[Dict[str, int]]] = None,
    zone_ids: Optional[Dict[str, int]] = None,
    n_zones: Optional[int] = None,
    zone_term: Optional[np.ndarray] = None,
    zone_up: Optional[np.ndarray] = None,
) -> Tuple[SoAFleetState, List[List[Optional[Instance]]]]:
    """Convert python ``Host`` objects to a persistent ``SoAFleetState``.

    ``slot_assignment`` optionally fixes the slot index of each preemptible
    instance per host (id → slot); the default packs them sorted by id.  The
    parity tests use it to rebuild with the exact slot layout the incremental
    path produced, making the comparison bit-exact.

    ``zone_ids`` optionally fixes the zone-name → id mapping (default:
    insertion order of ``Host.zone``); ``n_zones`` widens the accumulator
    arrays beyond the mapped zones.  ``zone_term``/``zone_up`` seed the
    per-zone T/U churn accumulators (both (Z,) float32; default zeros) —
    oracle rebuilds pass the incremental path's accumulator history here so
    churn-aware decisions compare bit-exact.
    """
    n = len(hosts)
    d, free_f, free_n, schedulable, domain, slow, pre_lists = _hosts_to_arrays(
        hosts, k_slots, domain_ids
    )
    if zone_ids is None:
        zone_ids = {}
        for h in hosts:
            zone_ids.setdefault(h.zone, len(zone_ids))
    host_zone = np.zeros((n,), np.int32)
    for i, h in enumerate(hosts):
        if h.zone not in zone_ids:
            raise ValueError(
                f"host {h.name} is in unknown zone {h.zone!r}; "
                f"known: {sorted(zone_ids)}"
            )
        host_zone[i] = zone_ids[h.zone]
    z = int(n_zones) if n_zones is not None else max(len(zone_ids), 1)
    if zone_ids and max(zone_ids.values()) >= z:
        raise ValueError(
            f"zone id {max(zone_ids.values())} out of range for n_zones={z}"
        )
    if zone_term is None:
        zone_term = np.zeros((z,), np.float32)
    if zone_up is None:
        zone_up = np.zeros((z,), np.float32)
    inst_res = np.zeros((n, k_slots, d), np.float32)
    inst_start = np.zeros((n, k_slots), np.float32)
    inst_price = np.ones((n, k_slots), np.float32)
    inst_ckpt = np.zeros((n, k_slots), np.float32)
    inst_cost_kind = np.full((n, k_slots), -1, np.int32)
    inst_period = np.full((n, k_slots), -1.0, np.float32)
    inst_valid = np.zeros((n, k_slots), bool)
    slots: List[List[Optional[Instance]]] = []
    for i, pre in enumerate(pre_lists):
        row: List[Optional[Instance]] = [None] * k_slots
        for k, inst in enumerate(pre):
            if slot_assignment is not None:
                k = slot_assignment[i][inst.id]
            if row[k] is not None:
                raise ValueError(
                    f"slot collision on host {hosts[i].name} slot {k}"
                )
            row[k] = inst
            inst_res[i, k] = inst.resources.vec
            inst_start[i, k] = inst.start_time
            inst_price[i, k] = inst.price_rate
            inst_ckpt[i, k] = (
                inst.last_checkpoint
                if inst.last_checkpoint is not None
                else inst.start_time
            )
            if inst.cost_kind is not None:
                if inst.cost_kind not in COST_KIND_IDS:
                    raise ValueError(
                        f"instance {inst.id} bills by unknown cost kind "
                        f"{inst.cost_kind!r}"
                    )
                inst_cost_kind[i, k] = COST_KIND_IDS[inst.cost_kind]
            if inst.period is not None:
                inst_period[i, k] = float(inst.period)
            inst_valid[i, k] = True
        slots.append(row)
    state = SoAFleetState(
        free_f=jnp.asarray(free_f),
        free_n=jnp.asarray(free_n),
        schedulable=jnp.asarray(schedulable),
        domain=jnp.asarray(domain),
        slow=jnp.asarray(slow),
        inst_res=jnp.asarray(inst_res),
        inst_start=jnp.asarray(inst_start),
        inst_price=jnp.asarray(inst_price),
        inst_ckpt=jnp.asarray(inst_ckpt),
        inst_cost_kind=jnp.asarray(inst_cost_kind),
        inst_period=jnp.asarray(inst_period),
        inst_valid=jnp.asarray(inst_valid),
        host_zone=jnp.asarray(host_zone),
        # copy, never alias: callers seed these with a LIVE state's buffers
        # (oracle rebuilds), and the transitions donate their inputs
        zone_term=jnp.array(np.asarray(zone_term), dtype=jnp.float32),
        zone_up=jnp.array(np.asarray(zone_up), dtype=jnp.float32),
    )
    return state, slots


# -- pure transitions (all O(K·D) scatter updates; fully jit-able) -----------
#
# Every transition donates the input state's buffers: the caller's reference
# is consumed and must be rebound to the returned state (the ``SoAFleet``
# mirror and the simulators do exactly that).


@jax.named_scope("transition")
def _apply_decision(
    state: SoAFleetState,
    host_idx: jax.Array,      # () int32
    mask_idx: jax.Array,      # () int32 subset-mask index (bit k = slot k)
    ok: jax.Array,            # () bool — no-op when False
    req_res: jax.Array,       # (D,)
    preemptible: jax.Array,   # () bool
    now: jax.Array,           # () float
    price: jax.Array,         # () float
    cost_kind: jax.Array,     # () int32 kind id; -1 = policy default
    period: jax.Array,        # () float billing period; -1 = policy default
) -> Tuple[SoAFleetState, jax.Array, jax.Array]:
    """Apply one decision: evacuate the winning subset, place the request.

    Returns ``(state', slot, kill)`` where ``slot`` is the slot index a
    preemptible placement landed in (undefined for normal/failed requests)
    and ``kill`` the (K,) bool mask of terminated slots on ``host_idx``.

    Scheduler-driven evacuations are involuntary from the victims' point of
    view, so the winner's zone T/U accumulators absorb the kill count and
    the victims' accrued uptime — the same churn signal storms feed.
    """
    k = state.k_slots
    row_valid = state.inst_valid[host_idx]                       # (K,)
    mask_bits = ((mask_idx >> jnp.arange(k)) & 1) > 0            # (K,)
    kill = mask_bits & row_valid & ok & ~preemptible
    freed = jnp.sum(
        jnp.where(kill[:, None], state.inst_res[host_idx], 0.0), axis=0
    )                                                            # (D,)
    take = jnp.where(ok, req_res, 0.0)
    free_f = state.free_f.at[host_idx].add(freed - take)
    free_n = state.free_n.at[host_idx].add(
        -jnp.where(ok & ~preemptible, req_res, 0.0)
    )
    valid_after = row_valid & ~kill
    slot = jnp.argmin(valid_after).astype(jnp.int32)             # first free
    place = ok & preemptible
    onehot = (jnp.arange(k) == slot) & place                     # (K,)
    z = state.host_zone[host_idx]
    n_kill = jnp.sum(kill.astype(jnp.float32))
    lost_up = jnp.sum(
        jnp.where(kill, now - state.inst_start[host_idx], 0.0)
    )
    new_state = dataclasses.replace(
        state,
        free_f=free_f,
        free_n=free_n,
        inst_valid=state.inst_valid.at[host_idx].set(valid_after | onehot),
        inst_res=state.inst_res.at[host_idx].set(
            jnp.where(onehot[:, None], req_res[None, :], state.inst_res[host_idx])
        ),
        inst_start=state.inst_start.at[host_idx].set(
            jnp.where(onehot, now, state.inst_start[host_idx])
        ),
        inst_price=state.inst_price.at[host_idx].set(
            jnp.where(onehot, price, state.inst_price[host_idx])
        ),
        inst_ckpt=state.inst_ckpt.at[host_idx].set(
            jnp.where(onehot, now, state.inst_ckpt[host_idx])
        ),
        inst_cost_kind=state.inst_cost_kind.at[host_idx].set(
            jnp.where(
                onehot,
                jnp.asarray(cost_kind, jnp.int32),
                state.inst_cost_kind[host_idx],
            )
        ),
        inst_period=state.inst_period.at[host_idx].set(
            jnp.where(
                onehot,
                jnp.asarray(period, jnp.float32),
                state.inst_period[host_idx],
            )
        ),
        zone_term=state.zone_term.at[z].add(n_kill),
        zone_up=state.zone_up.at[z].add(lost_up),
    )
    return new_state, slot, kill


def _step_core(
    state: SoAFleetState,
    req_res, req_preemptible, req_domain, now, price, req_cost_kind,
    req_period, policy: SchedulerPolicy, req_exclude=None, mult_val=None,
):
    inst_cost = fleet_slot_costs(state, now, policy)
    # The learned per-host churn rate ẑ is derived from the zone T/U
    # accumulators fresh each step (statically dropped for churn-blind
    # policies — the compiled program is then the exact pre-churn one).
    churn = (
        churn_of(state.zone_term, state.zone_up, state.host_zone)
        if policy.churn_aware
        else None
    )
    host_idx, mask_idx, ok, fell_back, margin = _decision_core(
        state.free_f, state.free_n, state.schedulable, state.domain,
        state.slow, state.inst_res, inst_cost, state.inst_valid,
        req_res, req_preemptible, req_domain,
        policy, require_free_slot=True, churn=churn,
        host_zone=state.host_zone if req_exclude is not None else None,
        exclude_zone=req_exclude, mult_val=mult_val,
    )
    state, slot, kill = _apply_decision(
        state, host_idx, mask_idx, ok, req_res, req_preemptible, now, price,
        req_cost_kind, req_period,
    )
    return state, (host_idx, slot, ok, kill, fell_back, margin)


_STEP_STATICS = ("policy",)


def _step_entry(state, req_res, req_preemptible, req_domain, now, price,
                req_cost_kind, req_period, req_exclude, *, policy):
    return _step_core(
        state, req_res, req_preemptible, req_domain, now, price,
        req_cost_kind, req_period, policy, req_exclude=req_exclude,
    )


def _many_entry(state, req_res, req_preemptible, req_domain, req_now,
                req_price, req_cost_kind, req_period, req_exclude, *, policy):
    def body(st, xs):
        res, pre, dom, now, price, kind, period, excl = xs
        return _step_core(
            st, res, pre, dom, now, price, kind, period, policy,
            req_exclude=excl,
        )

    return jax.lax.scan(
        body, state,
        (req_res, req_preemptible, req_domain, req_now, req_price,
         req_cost_kind, req_period, req_exclude),
    )


def _scan_live_rows(body, carry, xs, take, dead):
    """``lax.scan(body, carry, xs)`` over the taken rows only.

    A drain's ``take`` (B,) is a prefix of its batch (``queue_select``
    sorts invalid entries last), so the loop runs rows ``[0, sum(take))``
    and never the padding after them.  A row it skips leaves the carry
    untouched and reads ``dead`` in every output (a pytree of one row's
    outputs, broadcast over the B rows).  Under ``vmap`` the loop runs to
    the largest live count among the lanes, never past B; a lane that is
    done keeps its carry.
    """
    b = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = jax.tree_util.tree_map(
        lambda d: jnp.broadcast_to(d, (b,) + jnp.shape(d)), dead
    )

    def row(i, acc):
        c, ys = acc
        x = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), xs
        )
        c, y = body(c, x)
        ys = jax.tree_util.tree_map(
            lambda buf, v: jax.lax.dynamic_update_index_in_dim(buf, v, i, 0),
            ys, y,
        )
        return c, ys

    n_live = jnp.sum(take.astype(jnp.int32))
    return jax.lax.fori_loop(0, n_live, row, (carry, ys))


_step_donated = functools.partial(
    jax.jit, static_argnames=_STEP_STATICS, donate_argnums=(0,)
)(_step_entry)
_step_kept = functools.partial(jax.jit, static_argnames=_STEP_STATICS)(_step_entry)
_many_donated = functools.partial(
    jax.jit, static_argnames=_STEP_STATICS, donate_argnums=(0,)
)(_many_entry)
_many_kept = functools.partial(jax.jit, static_argnames=_STEP_STATICS)(_many_entry)


def schedule_step(
    state: SoAFleetState,
    req_res: jax.Array,          # (D,)
    req_preemptible: jax.Array,  # () bool
    req_domain: jax.Array,       # () int32; -1 = any
    now: jax.Array,              # () float
    price: jax.Array,            # () float
    policy: Optional[SchedulerPolicy] = None,
    req_cost_kind: jax.Array = -1,  # () int32 kind id; -1 = policy default
    donate: Optional[bool] = None,
    req_period: jax.Array = -1.0,  # () float period (s); -1 = policy default
    req_exclude_zone: jax.Array = -1,  # () int32 zone id; -1 = none
) -> Tuple[SoAFleetState, Tuple[jax.Array, ...]]:
    """Fused decide-and-apply on the persistent state (one dispatch/event).

    Returns ``(state', (host_idx, slot, ok, kill, fell_back, margin))`` — a
    6-tuple: the winning host index, the slot a preemptible placement landed
    in, whether the request was placed at all, the (K,) bool mask of slots
    evacuated on the winner, and the two shortlist-health signals (see
    ``_decision_core``) the adaptive controller consumes.

    ``policy`` (a ``SchedulerPolicy``) is the one static knob bundle: cost
    table + period, weigher multipliers, shortlist M, and the execution
    backends; equal policies share a single compile-cache entry.
    ``req_cost_kind`` tags the billing kind recorded on a preemptible
    placement (``COST_KIND_IDS``; -1 = the policy's default) — the
    per-request half of the mixed-payment model.  ``req_period`` likewise
    records the request's contract billing period (seconds; -1 = the
    policy's shared ``period``) into the ``inst_period`` column.
    ``req_exclude_zone`` (zone id; -1 = none) hard-filters one failure zone
    out of the decision — the relocation plane's operand; it is read only
    when ``policy.relocation_on`` (off-policies compile the exact
    pre-relocation program).

    With ``donate`` unset the policy's ``donate`` field applies (default
    True): the input state's buffers are reused for the output — the caller
    must not touch ``state`` afterwards; pass ``donate=False`` to keep the
    input alive (oracle comparisons, repeated benchmarks).  ``policy.mesh``
    shards stage 1 host-major across devices (the state should already be
    padded + placed via ``fleet_sharding``).
    """
    policy = ensure_policy(policy, "schedule_step")
    if donate is None:
        donate = policy.donate
    fn = _step_donated if donate else _step_kept
    return fn(
        state, req_res, req_preemptible, req_domain,
        jnp.asarray(now, jnp.float32), jnp.asarray(price, jnp.float32),
        jnp.asarray(req_cost_kind, jnp.int32),
        jnp.asarray(req_period, jnp.float32),
        jnp.asarray(req_exclude_zone, jnp.int32), policy=policy,
    )


def schedule_many(
    state: SoAFleetState,
    req_res: jax.Array,          # (B, D)
    req_preemptible: jax.Array,  # (B,) bool
    req_domain: jax.Array,       # (B,) int32; -1 = any
    req_now: jax.Array,          # (B,) float — each request's arrival time
    req_price: jax.Array,        # (B,) float
    policy: Optional[SchedulerPolicy] = None,
    req_cost_kind: Optional[jax.Array] = None,  # (B,) int32; None = defaults
    donate: Optional[bool] = None,
    req_period: Optional[jax.Array] = None,  # (B,) float; None = defaults
    req_exclude_zone: Optional[jax.Array] = None,  # (B,) int32; None = none
) -> Tuple[SoAFleetState, Tuple[jax.Array, ...]]:
    """Run a request batch through ``lax.scan`` carrying the fleet state, so
    each decision sees every earlier placement/termination in the batch —
    bit-identical to ``schedule_step`` in a loop, at one dispatch per batch.

    Returns ``(state', (host_idx (B,), slot (B,), ok (B,), kill (B, K),
    fell_back (B,), margin (B,)))`` — the batched 6-tuple of
    ``schedule_step``.  ``fell_back.sum()`` is the batch's
    admissibility-fallback counter and ``margin`` the per-decision headroom
    — the signals the adaptive shortlist controller steers M with.
    ``policy`` / ``req_cost_kind`` (per-request billing kinds) / ``donate``
    semantics as in ``schedule_step`` (the sharded stage 1 runs inside the
    scan body; the carried state stays sharded).
    """
    policy = ensure_policy(policy, "schedule_many")
    if donate is None:
        donate = policy.donate
    if req_cost_kind is None:
        req_cost_kind = jnp.full(jnp.shape(req_now), -1, jnp.int32)
    if req_period is None:
        req_period = jnp.full(jnp.shape(req_now), -1.0, jnp.float32)
    if req_exclude_zone is None:
        req_exclude_zone = jnp.full(jnp.shape(req_now), -1, jnp.int32)
    fn = _many_donated if donate else _many_kept
    return fn(
        state, req_res, req_preemptible, req_domain,
        jnp.asarray(req_now, jnp.float32), jnp.asarray(req_price, jnp.float32),
        jnp.asarray(req_cost_kind, jnp.int32),
        jnp.asarray(req_period, jnp.float32),
        jnp.asarray(req_exclude_zone, jnp.int32), policy=policy,
    )


def _reloc_entry(state, v_host, v_slot, v_on, req_res, req_domain,
                 req_cost_kind, req_period, req_price, req_exclude, now,
                 *, policy):
    k = state.inst_valid.shape[1]
    slot_ids = jnp.arange(k)

    def body(st, xs):
        vh, vs, on, res, dom, kind, period, price, excl = xs
        # 1. checkpoint FIRST (never-worse: the replacement restarts from
        #    here, and a storm racing the move loses only the work since
        #    this instant) — gated on `on` so padding rows are no-ops.
        row = jnp.where((slot_ids == vs) & on, now, st.inst_ckpt[vh])
        st = dataclasses.replace(st, inst_ckpt=st.inst_ckpt.at[vh].set(row))
        # 2. re-place through the ordinary pipeline, source zone excluded.
        st, (h, s, ok, _kill, fb, mg) = _step_core(
            st, res, jnp.asarray(True), dom, now, price, kind, period,
            policy, req_exclude=excl,
        )
        # 3. make-before-break: the victim departs only once its
        #    replacement is live (voluntary — a move is not churn, so the
        #    source zone's T numerator is untouched while U still accrues).
        mask = (slot_ids == vs) & on & ok
        st = apply_termination(st, vh, mask, now=now, involuntary=False)
        return st, (h, s, ok, fb, mg)

    return jax.lax.scan(
        body, state,
        (v_host, v_slot, v_on, req_res, req_domain, req_cost_kind,
         req_period, req_price, req_exclude),
    )


_reloc_donated = functools.partial(
    jax.jit, static_argnames=_STEP_STATICS, donate_argnums=(0,)
)(_reloc_entry)
_reloc_kept = functools.partial(jax.jit, static_argnames=_STEP_STATICS)(_reloc_entry)


def relocate_many(
    state: SoAFleetState,
    v_host: jax.Array,        # (B,) int32 — victim host index
    v_slot: jax.Array,        # (B,) int32 — victim slot on that host
    v_on: jax.Array,          # (B,) bool  — False = padding row (full no-op)
    req_res: jax.Array,       # (B, D) — replacement request sizes
    req_domain: jax.Array,    # (B,) int32; -1 = any
    req_cost_kind: jax.Array,  # (B,) int32 kind ids; -1 = policy default
    req_period: jax.Array,    # (B,) float32; -1 = policy default
    req_price: jax.Array,     # (B,) float32 — the victim's price rate
    req_exclude_zone: jax.Array,  # (B,) int32 — the source zone, hard-excluded
    now: jax.Array,           # () float — one relocation pass instant
    policy: Optional[SchedulerPolicy] = None,
    donate: Optional[bool] = None,
) -> Tuple[SoAFleetState, Tuple[jax.Array, ...]]:
    """One evacuation batch as ONE fused ``lax.scan`` dispatch: per victim,
    checkpoint → re-place (zone-excluded, always preemptible) → terminate
    the victim iff its replacement landed — the exact op sequence the
    per-victim ``schedule_step`` loop ran, so decisions are bit-identical
    to sequential evacuation while the dispatch count drops from one per
    victim to one per zone batch (the PR-8 follow-up).

    Returns ``(state', (host_idx (B,), slot (B,), ok (B,), fell_back (B,),
    margin (B,)))``; replacement requests are preemptible so they never
    kill (no ``kill`` column).  Padding rows (``v_on=False`` + sentinel
    unsatisfiable ``req_res``) leave the carried state bitwise untouched,
    exactly like ``schedule_many``'s padding."""
    policy = ensure_policy(policy, "relocate_many")
    if donate is None:
        donate = policy.donate
    fn = _reloc_donated if donate else _reloc_kept
    return fn(
        state, jnp.asarray(v_host, jnp.int32), jnp.asarray(v_slot, jnp.int32),
        jnp.asarray(v_on, bool), req_res, jnp.asarray(req_domain, jnp.int32),
        jnp.asarray(req_cost_kind, jnp.int32),
        jnp.asarray(req_period, jnp.float32),
        jnp.asarray(req_price, jnp.float32),
        jnp.asarray(req_exclude_zone, jnp.int32),
        jnp.asarray(now, jnp.float32), policy=policy,
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def apply_placement(
    state: SoAFleetState,
    host_idx: jax.Array,
    req_res: jax.Array,
    preemptible: jax.Array,
    now: jax.Array,
    price: jax.Array = 1.0,
    cost_kind: jax.Array = -1,  # () int32 kind id; -1 = policy default
    period: jax.Array = -1.0,   # () float period (s); -1 = policy default
) -> Tuple[SoAFleetState, jax.Array]:
    """Unconditionally place a request on ``host_idx`` (caller checked
    feasibility — e.g. re-applying a recorded decision, or initializing
    state without a rebuild).  Returns (state', slot).

    Precondition for preemptible placements: the host has a free slot
    (``~inst_valid[host_idx].all()``) — with all K slots valid, slot 0
    would be overwritten.  The decision paths (``schedule_step``)
    enforce this via ``require_free_slot``; direct callers must too."""
    take = req_res
    free_f = state.free_f.at[host_idx].add(-take)
    free_n = state.free_n.at[host_idx].add(
        -jnp.where(preemptible, jnp.zeros_like(take), take)
    )
    k = state.k_slots
    slot = jnp.argmin(state.inst_valid[host_idx]).astype(jnp.int32)
    onehot = (jnp.arange(k) == slot) & preemptible
    state = dataclasses.replace(
        state,
        free_f=free_f,
        free_n=free_n,
        inst_valid=state.inst_valid.at[host_idx].set(
            state.inst_valid[host_idx] | onehot
        ),
        inst_res=state.inst_res.at[host_idx].set(
            jnp.where(onehot[:, None], req_res[None, :], state.inst_res[host_idx])
        ),
        inst_start=state.inst_start.at[host_idx].set(
            jnp.where(onehot, jnp.asarray(now, jnp.float32), state.inst_start[host_idx])
        ),
        inst_price=state.inst_price.at[host_idx].set(
            jnp.where(onehot, jnp.asarray(price, jnp.float32), state.inst_price[host_idx])
        ),
        inst_ckpt=state.inst_ckpt.at[host_idx].set(
            jnp.where(onehot, jnp.asarray(now, jnp.float32), state.inst_ckpt[host_idx])
        ),
        inst_cost_kind=state.inst_cost_kind.at[host_idx].set(
            jnp.where(
                onehot,
                jnp.asarray(cost_kind, jnp.int32),
                state.inst_cost_kind[host_idx],
            )
        ),
        inst_period=state.inst_period.at[host_idx].set(
            jnp.where(
                onehot,
                jnp.asarray(period, jnp.float32),
                state.inst_period[host_idx],
            )
        ),
    )
    return state, slot


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("involuntary",))
def apply_termination(
    state: SoAFleetState,
    host_idx: jax.Array,
    slot_mask: jax.Array,  # (K,) bool — slots to evacuate (preempt/depart)
    now: Optional[jax.Array] = None,
    involuntary: bool = False,
) -> SoAFleetState:
    """Free the given preemptible slots on ``host_idx`` (h_n untouched —
    preemptible instances never counted there).

    With ``now`` given, the host's zone churn accumulators learn from the
    event: the evacuated slots' accrued uptime always feeds U, and
    ``involuntary=True`` (preemption storms, spot reclaims — anything the
    customer didn't ask for) additionally counts the kills into T.
    Voluntary departures therefore DILUTE the zone's learned churn rate ẑ =
    T/U, exactly as gce-manager's per-zone preemption rates behave.  Callers
    that omit ``now`` (legacy call sites) compile the exact pre-churn
    program and leave the accumulators untouched.
    """
    row_valid = state.inst_valid[host_idx]
    kill = slot_mask & row_valid
    freed = jnp.sum(
        jnp.where(kill[:, None], state.inst_res[host_idx], 0.0), axis=0
    )
    updates = dict(
        free_f=state.free_f.at[host_idx].add(freed),
        inst_valid=state.inst_valid.at[host_idx].set(row_valid & ~kill),
    )
    if now is not None:
        z = state.host_zone[host_idx]
        up = jnp.sum(
            jnp.where(
                kill,
                jnp.asarray(now, jnp.float32) - state.inst_start[host_idx],
                0.0,
            )
        )
        updates["zone_up"] = state.zone_up.at[z].add(up)
        if involuntary:
            n_kill = jnp.sum(kill.astype(jnp.float32))
            updates["zone_term"] = state.zone_term.at[z].add(n_kill)
    return dataclasses.replace(state, **updates)


@functools.partial(jax.jit, donate_argnums=(0,))
def apply_departure(
    state: SoAFleetState,
    host_idx: jax.Array,
    res: jax.Array,  # (D,) resources of the departing NORMAL instance
) -> SoAFleetState:
    """Voluntary departure of a normal instance (both views regain ``res``).
    Preemptible departures go through ``apply_termination`` with the slot."""
    return dataclasses.replace(
        state,
        free_f=state.free_f.at[host_idx].add(res),
        free_n=state.free_n.at[host_idx].add(res),
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def apply_checkpoint(
    state: SoAFleetState,
    host_idx: jax.Array,
    slot: jax.Array,
    now: jax.Array,
) -> SoAFleetState:
    """Record a durable checkpoint for the preemptible instance in ``slot``:
    from ``now`` on, its recompute cost accrues from this anchor (the
    device-resident counterpart of ``Instance.last_checkpoint``)."""
    return dataclasses.replace(
        state,
        inst_ckpt=state.inst_ckpt.at[host_idx, slot].set(
            jnp.asarray(now, jnp.float32)
        ),
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def set_schedulable(
    state: SoAFleetState, host_idx: jax.Array, value: jax.Array
) -> SoAFleetState:
    return dataclasses.replace(
        state, schedulable=state.schedulable.at[host_idx].set(value)
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def set_slow_factor(
    state: SoAFleetState, host_idx: jax.Array, value: jax.Array
) -> SoAFleetState:
    return dataclasses.replace(state, slow=state.slow.at[host_idx].set(value))


@functools.partial(jax.jit, donate_argnums=(0,))
def apply_host_failure(
    state: SoAFleetState,
    host_idx: jax.Array,
    normal_res: jax.Array,  # (D,) total resources of the host's NORMAL instances
    now: Optional[jax.Array] = None,
) -> SoAFleetState:
    """Hard host failure: mark unschedulable, evacuate every slot, release
    the normal aggregate (the python mirror terminates the Instance records).

    With ``now`` given the failure is learned as involuntary churn in the
    host's zone: every occupied slot's accrued uptime feeds U and its kill
    feeds T (callers omitting ``now`` keep the legacy churn-blind program).
    """
    row_valid = state.inst_valid[host_idx]
    freed = jnp.sum(
        jnp.where(row_valid[:, None], state.inst_res[host_idx], 0.0), axis=0
    )
    updates = dict(
        schedulable=state.schedulable.at[host_idx].set(False),
        free_f=state.free_f.at[host_idx].add(freed + normal_res),
        free_n=state.free_n.at[host_idx].add(normal_res),
        inst_valid=state.inst_valid.at[host_idx].set(
            jnp.zeros_like(row_valid)
        ),
    )
    if now is not None:
        z = state.host_zone[host_idx]
        up = jnp.sum(
            jnp.where(
                row_valid,
                jnp.asarray(now, jnp.float32) - state.inst_start[host_idx],
                0.0,
            )
        )
        updates["zone_up"] = state.zone_up.at[z].add(up)
        updates["zone_term"] = state.zone_term.at[z].add(
            jnp.sum(row_valid.astype(jnp.float32))
        )
    return dataclasses.replace(state, **updates)


# ---------------------------------------------------------------------------
# Drop-in scheduler wrapper (same .schedule() contract as the python ones)
# ---------------------------------------------------------------------------


class JaxPreemptibleScheduler:
    """Beyond-paper vectorized scheduler with the python-class interface.

    For apples-to-apples latency benchmarks against the python schedulers it
    rebuilds device arrays from the python hosts per call unless the caller
    maintains the SoA state incrementally (``schedule_soa``).
    """

    def __init__(
        self,
        cost_fn: Optional[CostFunction] = None,
        k_slots: int = 8,
        policy: Optional[SchedulerPolicy] = None,
        zone_rates: Optional[Dict[str, float]] = None,
    ):
        #: the one static knob bundle; ``policy.mesh`` note: the rebuild
        #: path does not pad, so sharding only engages when the host count
        #: already divides the mesh with ≥ M+1 hosts per shard; the
        #: persistent path (SoAFleet(mesh=...)) pads automatically.
        self.policy = ensure_policy(
            policy, "JaxPreemptibleScheduler", cost_fn=cost_fn
        )
        #: python cost module used to translate winning masks back into
        #: ``TerminationPlan`` costs (and to freeze slot costs at rebuild);
        #: derived from the policy's cost table when not given explicitly.
        self.cost_fn = cost_fn or self.policy.make_cost_fn()
        self.k_slots = k_slots
        #: frozen per-zone churn rates ẑ (zone name → rate) baked into each
        #: rebuild's ``churn`` column — the oracle counterpart of the
        #: persistent path's online-learned zone accumulators.
        self.zone_rates = dict(zone_rates) if zone_rates is not None else None

    # -- full pipeline from python objects ------------------------------------
    def schedule(
        self, req: Request, hosts: Sequence[Host], now: float
    ) -> ScheduleResult:
        # Zone ids by insertion order of Host.zone — the same derivation
        # rule SoAFleet/build_fleet_state use, so an exclusion id resolved
        # here names the same zone the persistent path excludes.
        zone_ids: Dict[str, int] = {}
        for h in hosts:
            zone_ids.setdefault(h.zone, len(zone_ids))
        state, slots = build_soa_state(
            hosts, now, cost_fn=self.cost_fn, k_slots=self.k_slots,
            zone_rates=self.zone_rates, zone_ids=zone_ids,
        )
        domains = {h.domain: i for i, h in enumerate({h.domain: h for h in hosts}.values())}
        dom = -1
        if req.domain is not None:
            dom = domains.get(req.domain, -1)
        excl = -1
        if req.exclude_zone is not None:
            # An unknown zone name excludes nothing (nothing to flee from).
            excl = zone_ids.get(req.exclude_zone, -1)
        host_idx, mask_idx, ok = self.schedule_soa(
            state,
            jnp.asarray(req.resources.vec, jnp.float32),
            bool(req.preemptible),
            dom,
            exclude_zone=excl,
        )
        if not bool(ok):
            return ScheduleResult(request=req, host=None, passes=1)
        hi = int(host_idx)
        mask = int(mask_idx)
        victims = tuple(
            slots[hi][k] for k in range(len(slots[hi])) if (mask >> k) & 1
        )
        plan = (
            EMPTY_PLAN
            if not victims
            else TerminationPlan(
                instances=victims,
                cost=self.cost_fn.cost(victims, now),
                feasible=True,
            )
        )
        return ScheduleResult(request=req, host=hosts[hi].name, plan=plan, passes=1)

    # -- jit'd core (device arrays in/out) -------------------------------------
    def schedule_soa(self, state: SoAHostState, req_res, preemptible: bool,
                     domain: int = -1, exclude_zone: int = -1):
        return schedule_decision(
            state,
            req_res,
            jnp.asarray(preemptible),
            jnp.asarray(domain, jnp.int32),
            policy=self.policy,
            req_exclude_zone=jnp.asarray(exclude_zone, jnp.int32),
        )
