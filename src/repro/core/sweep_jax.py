"""JAX-compiled Monte-Carlo sweep engine: B campaigns as one lax.scan.

``engine="jax"`` is the fourth engine behind :func:`repro.core.api.run`.
Where the numpy batched engine (core/sweep.py) mutates dynamic
per-instance row sets from Python each tick, this engine compiles the
whole campaign to one jitted ``lax.scan`` over ticks with lane-parallel
*count-plane* state.  Instances within a (lane, group, progress-step)
cell are exchangeable — same hazard, same hourly rate, same matcher
treatment — so the state is how many instances occupy each cell, not
which: ``idle``/``pilot-dead`` counts per (lane, group), ``busy`` job
counts per (lane, group, dt-progress-step), the CE queue as per-lane
checkpoint-level counts, and budgets/counters as lane columns.  That
makes every per-tick phase a fixed-shape integer reduction, which is
what lets one compiled scan replace ~1e6 Python-driven row updates and
makes 1024-lane planning grids routine.  Per-lane randomness is
``threefry`` (fold the tick index into each lane's key), not PCG64.

The hot per-tick ops — preemption fan-out, the queue->pilot matcher,
pilot progress sync, the billing/ledger reduction — are the Pallas
kernels in kernels/campaign_sweep.py (``use_pallas=True``, default on
TPU); on CPU the engine runs their jnp oracles from kernels/ref.py
directly (the kernels' interpret mode is pinned equal in
tests/test_kernels.py).

**The compiled-timeline segment splitter.**  ``lax.scan`` cannot branch
on Python timeline events mid-trace, so the spec timeline is compiled
(via the core/timeline.py registry) into *segments*: the union of all
lanes' event fire ticks splits the campaign into spans of constant
control parameters, and every per-segment parameter plane (rates, caps,
outage, floor arming, workload level, scale targets) is precomputed by
driving a :class:`JaxLaneOps` adapter — a full ``EngineOps``
implementation over planner state — through the registry's own
``apply_op`` bodies.  The scan then just gathers ``plane[seg_of_tick]``.
The one data-dependent event, the budget-floor cap, is handled in-scan:
each lane carries ``capped`` / ``cap_pending`` flags and its per-group
target vector, and scale targets come in *uncapped and capped* plane
pairs (the capped pair built with ``budget_capped=True``, so the
registry's own ``min(target, downscale)`` logic — and the
``outage_off`` exemption from it — is reused, not re-implemented).

**Equivalence tier: statistical, not bit-identical.**  The numpy
batched engine is pinned bit-identical to the solo engines; this engine
intentionally is not — per-group Poisson preemption totals with a
proportional systematic split replace per-instance PCG64 Bernoulli
draws, proportional allocation replaces row-age ordering for event
kills and pilot-order matching, and simultaneous same-tick scale chains
apply their net target.  The contract is
``tests/engine_equivalence.assert_statistically_equivalent``:
mean/p5/p95 bands on cost, GPU-days and jobs against the batched
engine over ``scenarios.default_suite`` (see README "Simulation
engines").  Event provenance is *not* statistical: ``events_fired`` is
reconstructed post-scan through the same registry records and matches
the other engines' schema exactly.
"""
from __future__ import annotations

import copy
import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import timeline as timeline_registry
from repro.core.spec import CampaignSpec
from repro.core.sweep import _Lane, _THRESHOLDS, _prepare

__all__ = ["JaxLaneOps", "JaxSweepEngine", "run_jax_detailed", "run_jax",
           "STAT_BANDS", "band_violations"]

#: the statistical-equivalence contract (README "Simulation engines"):
#: metric -> relative tolerance on the per-scenario mean against the
#: batched engine (and band-widening margin).  ``preemptions`` is
#: deliberately looser: this engine kills proportionally across
#: occupancy cells where the row engines kill newest-first, which
#: shifts how many of a tick's kills land on busy instances without
#: moving cost/throughput.
STAT_BANDS = {"cost": 0.02, "accel_days": 0.02, "jobs_finished": 0.02,
              "preemptions": 0.25, "egress_usd": 0.05}


def band_violations(ref: Dict[str, dict], got: Dict[str, dict],
                    bands: Optional[Dict[str, float]] = None) -> List[tuple]:
    """Where a ``SweepResult.summary`` ``got`` leaves the reference
    summary ``ref``'s bands: per scenario and metric, the means must
    agree within ``rel * |ref mean|`` and got's [p5, p95] must lie
    inside ref's widened by the same margin.  Returns
    ``(scenario, metric, "mean" | "band", ref stats, got stats)``
    tuples, empty when every band holds."""
    bands = STAT_BANDS if bands is None else bands
    out = []
    for scen in sorted(ref):
        for metric, rel in bands.items():
            a, b = ref[scen][metric], got[scen][metric]
            margin = rel * max(abs(a["mean"]), 1e-9)
            if abs(b["mean"] - a["mean"]) > margin:
                out.append((scen, metric, "mean", a, b))
            if not (a["p5"] - margin <= b["p5"]
                    and b["p95"] <= a["p95"] + margin):
                out.append((scen, metric, "band", a, b))
    return out


class JaxLaneOps:
    """One lane's :class:`~repro.core.timeline.EngineOps` adapter over
    *planner* state (prices, caps, targets, floor arming) instead of a
    live fleet.  The segment splitter drives it through the registry's
    shared ``apply`` bodies to precompute per-segment parameter planes —
    once with ``budget_capped=False`` and once ``=True`` so the scan can
    select the right scale target after a lane's floor fires — and the
    post-scan provenance pass drives it again to reconstruct
    ``events_fired`` records identical to the other engines'."""

    budget_capped = False
    downscale_target = 0

    def __init__(self, spec: CampaignSpec, pairs,
                 budget_capped: bool = False):
        G = len(pairs)
        self.budget_capped = bool(budget_capped)
        self.downscale_target = int(spec.downscale_target)
        self.floor_fraction = float(spec.budget_floor_fraction)
        self.rate_base = np.array(
            [((p.spot_price_per_day if spec.spot
               else p.ondemand_price_per_day) / 24.0) for p, _ in pairs])
        self.price_scale = 1.0
        self.curve = np.ones(G)
        self.cap = np.array([r.capacity for _, r in pairs], dtype=np.int64)
        self.outage = False
        self.min_queue = int(spec.min_queue)
        self.min_queue_eff = int(spec.min_queue)
        # net scale target set during the current segment (None: keep)
        self.scale_n: Optional[int] = None
        self.g_provider = [p.name for p, _ in pairs]
        self._prov_groups = {}
        for g, name in enumerate(self.g_provider):
            self._prov_groups.setdefault(name, []).append(g)
        # data-plane planner state (spec.dataplane): per-group origin
        # up/down for match gating and the cumulative miss-bandwidth
        # degrade factor the per-segment stage lengths are derived from
        self.origin_up = np.ones(G, dtype=bool)
        self.dp_degrade = np.ones(G)
        self.flush_edge = np.zeros(G, dtype=bool)
        self._dp_groups_by_base = {}
        for g, name in enumerate(self.g_provider):
            self._dp_groups_by_base.setdefault(
                name.split("/", 1)[0], []).append(g)
        self._dp_groups_by_base = {
            k: np.array(v, dtype=np.int64)
            for k, v in self._dp_groups_by_base.items()}

    def rate_h(self) -> np.ndarray:
        """Effective $/h per group — the engines' shared expression
        ``(base * shift scalar) * curve factor``."""
        return self.rate_base * self.price_scale * self.curve

    # -- EngineOps ---------------------------------------------------------
    def scale_to(self, n: int):
        self.scale_n = max(0, int(n))

    def deprovision_all(self):
        self.scale_n = 0

    def set_outage(self, on: bool):
        self.outage = bool(on)

    def scale_prices(self, factor: float):
        self.price_scale *= factor

    def set_price_factor(self, provider, factor: float):
        if provider is None:
            self.curve[:] = factor
        else:
            gs = self._prov_groups.get(provider)
            if gs is not None:          # unknown provider: no-op (solo
                self.curve[gs] = factor  # semantics)

    def scale_capacity(self, factor: float):
        self.cap = np.maximum(1, (self.cap * factor).astype(np.int64))

    def arm_budget_floor(self, fraction: float, target: int):
        self.floor_fraction = float(fraction)
        self.downscale_target = int(target)

    def set_workload_factor(self, factor: float):
        self.min_queue_eff = int(self.min_queue * factor)

    # -- data-plane ops (spec.OriginOutage/OriginDegrade/CacheFlush).
    #    Outage and degrade become per-segment parameter planes; a
    #    CacheFlush becomes a per-segment edge flag the scan folds into
    #    the first-stage-miss ("virgin") pool: the row engines' lazy
    #    epoch reset makes every live pilot's NEXT stage-in a forced
    #    miss, which the mixture model reproduces by marking the whole
    #    live population of the flushed provider's groups virgin.
    def set_origin_outage(self, provider: str, on: bool):
        gs = self._dp_groups_by_base.get(str(provider).split("/", 1)[0])
        if gs is not None:
            self.origin_up[gs] = not bool(on)

    def degrade_origin(self, provider: str, factor: float):
        gs = self._dp_groups_by_base.get(str(provider).split("/", 1)[0])
        if gs is not None:
            self.dp_degrade[gs] *= float(factor)

    def flush_cache(self, provider: str):
        gs = self._dp_groups_by_base.get(str(provider).split("/", 1)[0])
        if gs is not None:
            self.flush_edge[gs] = True


# -- the jitted tick scan --------------------------------------------------

def _kernel_ops(use_pallas: bool, consts):
    """The four hot ops, bound to either the Pallas kernels (TPU) or
    their jnp oracles (CPU) — identical integer semantics either way
    (tests/test_kernels.py pins kernel == ref)."""
    if use_pallas:
        from repro.kernels import ops as k

        def preempt(cells, kk):
            return k.campaign_preempt(cells, kk)

        def match(idle, kk):
            return k.campaign_match(idle, kk)

        def advance(busy, fm):
            return k.campaign_advance(busy, fm)

        def bill(live, rate):
            return k.campaign_bill(live, rate, consts["prov_onehot"])
    else:
        from repro.kernels import ref as r

        def preempt(cells, kk):
            return r.campaign_preempt_ref(cells, kk)

        def match(idle, kk):
            return r.campaign_match_ref(idle, kk)

        def advance(busy, fm):
            return r.campaign_advance_ref(busy, fm)

        def bill(live, rate):
            return r.campaign_bill_ref(live, rate, consts["prov_onehot"])
    return preempt, match, advance, bill


def _poisson(u, lam):
    """Poisson(lam) quantile of the uniform draw ``u``: truncated
    inverse-CDF for small lam, a rounded normal approximation for large
    (statistical tier; per-tick per-group lam is O(1) in practice)."""
    from jax.scipy.special import ndtri
    K = 24
    with jax.named_scope("poisson"):
        p = jnp.exp(-jnp.minimum(lam, 30.0))
        cdf = p
        kk = (u > cdf).astype(jnp.int32)
        for j in range(1, K):
            p = p * lam / j
            cdf = cdf + p
            kk = kk + (u > cdf).astype(jnp.int32)
        z = ndtri(jnp.clip(u, 1e-7, 1.0 - 1e-7))
        k_norm = jnp.round(lam + jnp.sqrt(jnp.maximum(lam, 0.0)) * z)
        return jnp.where(lam > 8.0,
                         jnp.maximum(k_norm, 0.0).astype(jnp.int32), kk)


#: precision of the count-plane matmuls (counts and $ against one-hot
#: maps): a default-precision f32 matmul on a TPU's MXU rounds its
#: inputs to bf16, which is exact for counts only up to 256
_EXACT = jax.lax.Precision.HIGHEST


def _state_shapes(B: int, G: int, W: int, L: int, P: int
                  ) -> Dict[str, Tuple[tuple, str]]:
    """The scan's carry, name -> (shape, dtype); its outputs are the
    final carry and ``live_g``."""
    i, f, b = "int32", "float32", "bool"
    return {
        "idle": ((B, G), i), "pdead": ((B, G), i), "busy": ((B, G, W), i),
        "target_g": ((B, G), i), "lv": ((B, L), i), "fresh_q": ((B,), i),
        "spent": ((B,), f), "by_prov": ((B, P), f), "infra": ((B,), f),
        "fired": ((B, len(_THRESHOLDS)), b), "capped": ((B,), b),
        "cap_pending": ((B,), b), "cap_tick": ((B,), i),
        "pre_ct": ((B,), i), "nat_ct": ((B,), i), "fin_ct": ((B,), i),
        "accel": ((B,), f), "busy_h": ((B,), f), "busy_prov": ((B, P), f),
        "hit_acc": ((B, G), f), "virgin": ((B, G), f), "hits": ((B,), f),
        "misses": ((B,), f), "stage_t": ((B,), f), "egress_g": ((B, G), f),
    }


def _scan(planes, consts, xs, *, nat_any, use_pallas,
          dp_gating=False, dp_staging=False):
    """One lax.scan over all N ticks of B lock-step lanes.

    The tick phases mirror ``BatchedFleetEngine.tick`` (see that
    module): events, kill-to-target, spawn, preemption, queue top-up,
    match, NAT drops, advance, billing, overhead, ledger thresholds,
    accumulation.  Billing charges the interval ending at this tick
    against the live set at the tick's *start*, which equals the numpy
    engine's ``live + died - created`` counter identity.

    Each phase is a ``jax.named_scope`` (``events``, ``kill``, ``spawn``,
    ``preempt``, ``topup``, ``match``, ``nat``, ``advance``, ``bill``,
    ``overhead``, ``ledger``, ``accumulate``), as are ``_poisson`` and
    the two preemption kernel call sites (``preempt_to_target``,
    ``preempt_sampled``): they name each device op in its ``op_name``
    metadata and change nothing else in the compiled program."""
    preempt_fn, match_fn, advance_fn, bill_fn = \
        _kernel_ops(use_pallas, consts)

    prov_onehot = consts["prov_onehot"]           # [G,P] f32
    pre_rate = consts["pre_rate_g"][None, :]      # [1,G] f32
    pre_scale = consts["pre_scale_g"][None, :]
    M_wl = consts["M_wl"]                         # [B,W,L] f32
    M_jw = consts["M_jw"]                         # [B,L+1,W] f32
    finmask_rg = consts["finmask_rg"]             # [B*G,W] i32
    nat_g = consts["nat_g"]                       # [B,G] i32
    overhead = consts["overhead"]                 # [B]
    budget = consts["budget"]                     # [B]
    dt = consts["dt"]                             # scalar f32
    thresholds = jnp.asarray(_THRESHOLDS, jnp.float32)
    B, G = nat_g.shape
    W = M_wl.shape[1]
    L = M_wl.shape[2]
    P = prov_onehot.shape[1]
    keys = jax.vmap(jax.random.PRNGKey)(consts["seeds"])

    def requeue_levels(kb):
        # busy cells [B,G,W] -> checkpoint-level counts [B,L]
        return jnp.matmul(kb.astype(jnp.float32), M_wl, precision=_EXACT) \
            .sum(axis=1).astype(jnp.int32)

    def split_cells(idle, pdead, busy, k, scope):
        # proportional fan-out of k removals per (lane, group) across
        # the group's occupancy cells (idle | pilot-dead | busy-at-w);
        # ``scope`` names the call site in the device trace's metadata
        with jax.named_scope(scope):
            cells = jnp.concatenate(
                [idle[..., None], pdead[..., None], busy], axis=2)
            killed = preempt_fn(cells.reshape(B * G, W + 2),
                                k.reshape(B * G)).reshape(B, G, W + 2)
            return killed[..., 0], killed[..., 1], killed[..., 2:]

    def step(c, x):
        i, seg, is_start = x
        idle, pdead, busy = c["idle"], c["pdead"], c["busy"]
        cap_g = planes["cap"][seg]                           # [B,G] i32
        rate_g = planes["rate"][seg]                         # [B,G] f32
        live0 = idle + pdead + busy.sum(axis=2)              # [B,G] i32
        live_g = live0
        virgin = c["virgin"]
        with jax.named_scope("events"):
            if dp_staging:
                # a CacheFlush edge marks the flushed provider's whole live
                # population virgin: the lazy epoch reset in the row engines
                # forces every pilot's next stage-in to miss
                virgin = jnp.where(
                    jnp.logical_and(is_start, planes["dp_flush"][seg]),
                    live0.astype(jnp.float32), virgin)

            # 1. events: the deferred budget cap first (solo at(now) order),
            # then this segment's net scale target (uncapped/capped pair)
            def greedy(n):                                   # [B] -> [B,G]
                cume = jnp.cumsum(cap_g, axis=1) - cap_g
                return jnp.clip(n[:, None] - cume, 0, cap_g)

            apply_cap = c["cap_pending"]
            target_g = jnp.where(apply_cap[:, None],
                                 greedy(planes["downscale"][seg]),
                                 c["target_g"])
            cap_tick = jnp.where(apply_cap, i, c["cap_tick"])
            n_eff = jnp.where(c["capped"], planes["n_cap"][seg],
                              planes["n_unc"][seg])
            do_scale = is_start & (n_eff >= 0)
            target_g = jnp.where(do_scale[:, None],
                                 greedy(jnp.maximum(n_eff, 0)), target_g)

        with jax.named_scope("kill"):
            # 2. kill down to target (event stops); busy kills requeue
            excess = jnp.clip(live_g - target_g, 0, None)
            ki, kp, kb = split_cells(idle, pdead, busy, excess,
                                     "preempt_to_target")
            idle, pdead, busy = idle - ki, pdead - kp, busy - kb
            pre_ct = c["pre_ct"] + kb.sum(axis=(1, 2))
            lv = c["lv"] + requeue_levels(kb)
            live_g = live_g - ki - kp - kb.sum(axis=2)
            if dp_staging:                     # kills hit virgins pro rata
                virgin = virgin * live_g.astype(jnp.float32) \
                    / jnp.maximum(1.0, live0.astype(jnp.float32))

        with jax.named_scope("spawn"):
            # 3. spawn to min(target, capacity); fresh pilots arrive idle
            deficit = jnp.clip(jnp.minimum(target_g, cap_g) - live_g,
                               0, None)
            idle = idle + deficit
            live_g = live_g + deficit
            if dp_staging:                     # fresh pilots stage cold
                virgin = virgin + deficit.astype(jnp.float32)
                live_sp = live_g

        with jax.named_scope("preempt"):
            # 4. preemption sampling: per-lane threefry keyed by the tick,
            # a Poisson total per (lane, group) from the shared fleet
            # hazard, fanned out across occupancy cells proportionally
            subkeys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(keys, i)
            u = jax.vmap(lambda kk: jax.random.uniform(kk, (G,)))(subkeys)
            util = live_g.astype(jnp.float32) \
                / jnp.maximum(1, cap_g).astype(jnp.float32)
            hazard = pre_rate * (1.0 + (pre_scale - 1.0) * util) * dt
            k_pre = _poisson(u, live_g.astype(jnp.float32) * hazard)
            ki, kp, kb = split_cells(idle, pdead, busy, k_pre,
                                     "preempt_sampled")
            idle, pdead, busy = idle - ki, pdead - kp, busy - kb
            pre_ct = pre_ct + kb.sum(axis=(1, 2))
            lv = lv + requeue_levels(kb)
            live_g = live_g - ki - kp - kb.sum(axis=2)
            if dp_staging:
                virgin = virgin * live_g.astype(jnp.float32) \
                    / jnp.maximum(1.0, live_sp.astype(jnp.float32))

        with jax.named_scope("topup"):
            # 5/6. top the CE queue up to the workload level
            ring_tot = lv.sum(axis=1)
            fresh_q = c["fresh_q"] + jnp.clip(
                planes["minq"][seg] - (ring_tot + c["fresh_q"]), 0, None)

        with jax.named_scope("match"):
            # 7. match k = min(idle, queued) jobs: the requeued ring drains
            # first (highest checkpoint level first), then fresh jobs; the
            # matcher splits k across groups by idle-pilot counts and the
            # joint (group x queue-slice) pairing is the overlap of the two
            # cumulative partitions of [0, k).  Origin outages remove the
            # gated groups' idle pilots from the matcher's input (they stay
            # idle and billed, exactly like the row engines' skip).
            if dp_gating:
                idle_m = idle * planes["origin_up"][seg]
            else:
                idle_m = idle
            idle_tot = idle_m.sum(axis=1)
            k = jnp.minimum(idle_tot, ring_tot + fresh_q)
            k = jnp.where(planes["outage"][seg], 0, k)
            take_g = match_fn(idle_m, k)                         # [B,G]
            avail = jnp.concatenate([lv[:, ::-1], fresh_q[:, None]], axis=1)
            cumq = jnp.cumsum(avail, axis=1)
            take_j = jnp.clip(k[:, None] - (cumq - avail), 0, avail)
            cA = jnp.cumsum(take_g, axis=1)
            cB = jnp.cumsum(take_j, axis=1)
            lo = jnp.maximum((cA - take_g)[:, :, None],
                             (cB - take_j)[:, None, :])
            hi = jnp.minimum(cA[:, :, None], cB[:, None, :])
            joint = jnp.clip(hi - lo, 0, None).astype(jnp.float32)
            if dp_staging:
                # stage-in as a count-axis front extension: a matched job
                # enters at S_max + w0 - S and reaches its old entry step
                # after S staging ticks.  The hit/miss split is the
                # deterministic per-(lane, group) fractional accumulator —
                # the mixture analogue of the row engines' per-pilot
                # rotation (long-run hit frequency exactly r, no RNG).
                # Each virgin (freshly spawned or freshly flushed) pilot
                # restarts its rotation at k=0, losing the fractional hit
                # credit a mid-rotation pilot carries — expected deficit
                # E[frac(n*r)] per reset (dp_loss_g) — charged the tick the
                # virgin first matches.
                take_f = take_g.astype(jnp.float32)
                first_f = jnp.minimum(take_f, virgin)
                virgin = virgin - first_f
                acc = c["hit_acc"] + take_f * consts["dp_r_g"][None, :] \
                    - first_f * consts["dp_loss_g"][None, :]
                th_f = jnp.clip(jnp.floor(acc), 0.0, take_f)
                hit_acc = acc - th_f
                cumj = jnp.cumsum(joint, axis=2)
                hit_j = jnp.clip(th_f[:, :, None] - (cumj - joint),
                                 0.0, joint)
                miss_j = joint - hit_j
                inc = (hit_j[..., None] * consts["E_hit"]).sum(axis=2) \
                    + (miss_j[..., None] * planes["E_miss"][seg]).sum(axis=2)
                busy = busy + inc.astype(jnp.int32)
                has = consts["dp_has_g"][None, :]
                miss_f = (take_f - th_f) * has
                hits = c["hits"] + (th_f * has).sum(axis=1)
                misses = c["misses"] + miss_f.sum(axis=1)
                stage_t = c["stage_t"] \
                    + (th_f * consts["S_hit_g"][None, :]
                       + (take_f - th_f)
                       * planes["S_miss"][seg].astype(jnp.float32)) \
                    .sum(axis=1)
                # cache-miss egress: usd/miss is precomputed (gb * price);
                # charged the tick the job matched, next to the GPU hours
                eg_g = (take_f - th_f) * consts["dp_usd_miss_g"][None, :]
                egress_g = c["egress_g"] + eg_g
            else:
                busy = busy + jnp.matmul(joint, M_jw, precision=_EXACT) \
                    .astype(jnp.int32)
                hit_acc, hits, misses = c["hit_acc"], c["hits"], c["misses"]
                stage_t, egress_g = c["stage_t"], c["egress_g"]
                eg_g = jnp.zeros_like(egress_g)
            idle = idle - take_g
            lv = lv - take_j[:, :L][:, ::-1]
            fresh_q = fresh_q - take_j[:, L]

        with jax.named_scope("nat"):
            # 7.5 NAT drops: every busy pilot in a disconnected group
            # requeues its job (instance stays alive and billed, pilot dead)
            nat_ct = c["nat_ct"]
            if nat_any:
                drop = busy * nat_g[:, :, None]
                cnt = drop.sum(axis=(1, 2))
                lv = lv + requeue_levels(drop)
                nat_ct = nat_ct + cnt
                pre_ct = pre_ct + cnt
                busy = busy - drop
                pdead = pdead + drop.sum(axis=2)

        with jax.named_scope("advance"):
            # 8. advance progress one dt step; finishes release the pilot
            adv, fin = advance_fn(busy.reshape(B * G, W), finmask_rg)
            busy = adv.reshape(B, G, W)
            fin_g = fin.reshape(B, G)
            fin_ct = c["fin_ct"] + fin_g.sum(axis=1)
            idle = idle + fin_g

        with jax.named_scope("bill"):
            # 9. bill the interval ending at this tick against the tick's
            # starting live set, at post-event rates (numpy counter identity)
            dh = jnp.where(i > 0, dt, 0.0)
            spent_d, prov_d = bill_fn(live0, rate_g * dh)
            spent = c["spent"] + spent_d + eg_g.sum(axis=1)
            by_prov = c["by_prov"] + prov_d

        with jax.named_scope("overhead"):
            # 10. flat infra overhead
            oh = overhead * dt / 24.0
            chg = oh > 0
            spent = spent + jnp.where(chg, oh, 0.0)
            infra = c["infra"] + jnp.where(chg, oh, 0.0)

        with jax.named_scope("ledger"):
            # 11. ledger alert thresholds -> budget-floor tripwire (the cap
            # itself applies at the next tick's event phase)
            frac = jnp.maximum(0.0, budget - spent) / budget
            cross = (frac[:, None] <= thresholds[None, :]) & ~c["fired"]
            newly = cross.any(axis=1)
            fired = c["fired"] | cross
            trigger = newly & (frac <= planes["floor"][seg]) & ~c["capped"]
            capped = c["capped"] | trigger

        with jax.named_scope("accumulate"):
            # 12. accumulate GPU-time totals at end-of-tick occupancy
            busy_g = busy.sum(axis=2).astype(jnp.float32)
            live_end = (idle + pdead).astype(jnp.float32) + busy_g
            accel = c["accel"] + live_end.sum(axis=1) * dt
            busy_h = c["busy_h"] + busy_g.sum(axis=1) * dt
            busy_prov = c["busy_prov"] \
                + jnp.matmul(busy_g, prov_onehot, precision=_EXACT) * dt

        return {"idle": idle, "pdead": pdead, "busy": busy,
                "target_g": target_g, "lv": lv, "fresh_q": fresh_q,
                "spent": spent, "by_prov": by_prov, "infra": infra,
                "fired": fired, "capped": capped, "cap_pending": trigger,
                "cap_tick": cap_tick, "pre_ct": pre_ct,
                "nat_ct": nat_ct, "fin_ct": fin_ct, "accel": accel,
                "busy_h": busy_h, "busy_prov": busy_prov,
                "hit_acc": hit_acc, "hits": hits, "misses": misses,
                "stage_t": stage_t, "egress_g": egress_g,
                "virgin": virgin}, None

    init = {k: jnp.zeros(shape, dtype) for k, (shape, dtype)
            in _state_shapes(B, G, W, L, P).items()}
    init["cap_tick"] = jnp.full((B,), -1, jnp.int32)
    out, _ = jax.lax.scan(step, init, xs)

    # settle the final interval: one more dt at last-segment rates
    live_final = out["idle"] + out["pdead"] + out["busy"].sum(axis=2)
    amt = live_final.astype(jnp.float32) * planes["rate"][-1] * dt
    out["spent"] = out["spent"] + amt.sum(axis=1)
    out["by_prov"] = out["by_prov"] \
        + jnp.matmul(amt, prov_onehot, precision=_EXACT)
    out["live_g"] = live_final
    return out


# -- one packed buffer each way --------------------------------------------
#
# A host<->device transfer costs about the same whatever its size, so the
# scan's arguments go to the device as one flat uint32 buffer and its
# outputs come back as another, in place of one transfer per array.
# Every array the scan takes or gives is 32-bit or bool: one word an
# element, bools as 0/1.  Each array starts on a row of 128 words, the
# lane width of a TPU tile: at unaligned starts the TPU compiler took
# about three times as long over the 1,020-lane batch's unpacking.

_WORD_DTYPES = ("float32", "int32", "uint32", "bool")
_ALIGN_WORDS = 128


def _layout(groups: Dict[str, dict]) -> tuple:
    """Where each array of ``groups`` (group -> key -> anything with a
    ``shape`` and a ``dtype``) lies in one packed buffer: a hashable
    tuple of ``(group, key, offset, shape, dtype)``, offsets in words,
    a function of keys, shapes and dtypes alone."""
    layout, off = [], 0
    for g, arrays in groups.items():
        for k, a in arrays.items():
            dtype = a.dtype.name
            if dtype not in _WORD_DTYPES:
                raise TypeError(f"{g}[{k!r}] is {dtype}: only "
                                f"{', '.join(_WORD_DTYPES)} pack")
            shape = tuple(a.shape)
            layout.append((g, k, off, shape, dtype))
            off += -(-math.prod(shape) // _ALIGN_WORDS) * _ALIGN_WORDS
    return tuple(layout)


def _pack(groups: Dict[str, dict], layout: tuple):
    """The arrays of ``groups`` in one uint32 buffer, placed as
    ``layout`` says, with zeros between them: host arrays fill one numpy
    buffer in place, traced ones are concatenated inside the jit (where
    the groups' keys, shapes and dtypes are checked against the
    layout's, in any order)."""
    arrays = [groups[g][k] for g, k, _off, _shape, _dtype in layout]
    if any(isinstance(a, jax.Array) for a in arrays):
        assert sorted(e[:2] + e[3:] for e in _layout(groups)) \
            == sorted(e[:2] + e[3:] for e in layout), "arrays, layout differ"
        words, end = [], 0
        for a, (_g, _k, off, _shape, dtype) in zip(arrays, layout):
            words.append(jnp.zeros(off - end, jnp.uint32))
            words.append((a.astype(jnp.uint32) if dtype == "bool" else
                          jax.lax.bitcast_convert_type(a, jnp.uint32))
                         .reshape(-1))
            end = off + a.size
        return jnp.concatenate(words)
    _g, _k, off, shape, _d = layout[-1]
    buf = np.empty(off + math.prod(shape), np.uint32)
    end = 0
    for a, (_g, _k, off, _shape, dtype) in zip(arrays, layout):
        a = np.asarray(a).reshape(-1)
        buf[end:off] = 0
        buf[off:off + a.size] = a if dtype == "bool" else a.view(np.uint32)
        end = off + a.size
    return buf


def _unpack(buf, layout: tuple) -> Dict[str, dict]:
    """The arrays ``layout`` places in ``buf``, by group and key: views
    of a numpy buffer, or inside a jit static slices of a traced one."""
    groups: Dict[str, dict] = {}
    for g, k, off, shape, dtype in layout:
        w = buf[off:off + math.prod(shape)].reshape(shape)
        if dtype == "bool":
            a = w != 0
        elif isinstance(w, np.ndarray):
            a = w.view(dtype)
        else:
            a = jax.lax.bitcast_convert_type(w, dtype)
        groups.setdefault(g, {})[k] = a
    return groups


@functools.lru_cache(maxsize=None)
def _out_layout(B: int, G: int, W: int, L: int, P: int) -> tuple:
    """The layout of the scan's packed outputs, from the batch's sizes."""
    shapes = dict(_state_shapes(B, G, W, L, P), live_g=((B, G), "int32"))
    return _layout({"out": {k: jax.ShapeDtypeStruct(shape, dtype)
                            for k, (shape, dtype) in shapes.items()}})


def _out_sizes(consts) -> Tuple[int, int, int, int, int]:
    """(B, G, W, L, P) of a batch, from its constants' shapes."""
    B, G = consts["nat_g"].shape
    _B, W, L = consts["M_wl"].shape
    return B, G, W, L, consts["prov_onehot"].shape[1]


@functools.partial(jax.jit, static_argnames=("layout", "nat_any",
                                             "use_pallas", "dp_gating",
                                             "dp_staging"))
def _scan_campaigns(buf, *, layout, nat_any, use_pallas,
                    dp_gating=False, dp_staging=False):
    """The scan's one jitted entry: unpacks ``buf`` (planes, constants
    and per-tick inputs, placed as ``layout`` says; see :func:`_pack`),
    runs :func:`_scan` and returns its outputs packed in one uint32
    buffer as ``_out_layout`` places them.  The optimization barrier
    hands the scan standalone arrays, so that XLA folds no slice of the
    buffer into the loop."""
    args = _unpack(buf, layout)
    planes, consts, xs = jax.lax.optimization_barrier(
        (args["planes"], args["consts"], tuple(args["xs"].values())))
    out = _scan(planes, consts, xs, nat_any=nat_any, use_pallas=use_pallas,
                dp_gating=dp_gating, dp_staging=dp_staging)
    return _pack({"out": out}, _out_layout(*_out_sizes(consts)))


# -- batch construction ----------------------------------------------------

def _distinct(specs: Sequence) -> Tuple[List[int], List[int]]:
    """Each spec's row among the distinct specs, and the position of
    each row's first spec.  Equal specs share a row whether or not they
    are one object; the identity check first spares hashing a spec per
    lane.  An unhashable spec gets a row of its own."""
    rows: Dict[object, int] = {}
    row_of_id: Dict[int, int] = {}
    u_of: List[int] = []
    firsts: List[int] = []
    for i, sc in enumerate(specs):
        u = row_of_id.get(id(sc))
        if u is None:
            try:
                u = rows.setdefault(sc, len(firsts))
            except TypeError:
                u = len(firsts)
            if u == len(firsts):
                firsts.append(i)
            row_of_id[id(sc)] = u
        u_of.append(u)
    return u_of, firsts


#: the scan outputs a lane's results dict is read from
_RESULT_FIELDS = ("busy_prov", "busy_h", "spent", "by_prov", "egress_g",
                  "infra", "live_g", "accel", "pre_ct", "nat_ct", "fin_ct",
                  "stage_t", "hits", "misses")

#: record values that a shallow copy of a provenance record copies
_ATOMS = (str, int, float, bool, type(None))


class JaxSweepEngine:
    """One lock-step batch of lanes compiled to a single scan (the JAX
    analogue of ``BatchedFleetEngine`` — same batching key, so the two
    engines chunk a sweep identically)."""

    def __init__(self, lanes: Sequence[_Lane],
                 use_pallas: Optional[bool] = None):
        self.lanes = list(lanes)
        B = len(self.lanes)
        ref = self.lanes[0]
        pairs = ref.pairs
        G = len(pairs)
        self.B, self.G = B, G
        self.dt = float(ref.spec.dt_h)
        self.duration = float(ref.spec.duration_h)
        if use_pallas is None:
            from repro.sharding_ctx import on_tpu
            use_pallas = on_tpu()
        self.use_pallas = bool(use_pallas)

        # static per-group config (identical across lanes by batch key)
        self.g_provider = [p.name for p, _ in pairs]
        self.providers: List[str] = []
        for name in self.g_provider:
            if name not in self.providers:
                self.providers.append(name)
        self.Pn = len(self.providers)
        pi = np.array([self.providers.index(n) for n in self.g_provider])
        prov_onehot = np.zeros((G, self.Pn), np.float32)
        prov_onehot[np.arange(G), pi] = 1.0
        self.provider_tflops = {p.name: p.fp32_tflops for p, _r in pairs}
        self.homogeneous = all(t is None
                               for t in self.provider_tflops.values())
        g_pre_rate = np.array([r.preempt_rate_per_hour for _, r in pairs],
                              np.float32)
        g_pre_scale = np.array([r.preempt_scale_at_full for _, r in pairs],
                               np.float32)
        g_nat = np.array([p.nat_idle_timeout_s for p, _ in pairs])

        # the same float tick walk as the numpy engines
        times = []
        now = 0.0
        while now < self.duration:
            times.append(now)
            now += self.dt
        self.tick_times = np.array(times)
        N = len(times)
        self.N = N

        # the bake is a function of the spec alone (pairs come from the
        # spec): it runs once per distinct spec, as row u of (U, ...)
        # arrays, and one gather over u_of_b gives each lane its row
        u_of, firsts = _distinct([ln.spec for ln in self.lanes])
        reps = [self.lanes[i] for i in firsts]
        U = len(reps)
        u_of_b = np.array(u_of, np.int64)
        self._row = u_of

        def at_lanes(a: np.ndarray, axis: int) -> np.ndarray:
            return a if U == B else np.take(a, u_of_b, axis=axis)

        obs.count("bake_lanes", B)
        obs.count("bake_specs", U)

        # compile timelines; segments = union of all specs' fire ticks
        evs_u: List[List[tuple]] = []
        fts_u: List[np.ndarray] = []
        seg_set = {0}
        for ln in reps:
            evs = timeline_registry.compile_timeline(ln.spec.timeline)
            ft = np.searchsorted(self.tick_times,
                                 np.array([e[0] for e in evs]), "left") \
                if evs else np.zeros(0, np.int64)
            evs_u.append(evs)
            fts_u.append(ft)
            seg_set.update(int(t) for t in ft if t < N)
        self._evs: List[List[tuple]] = [evs_u[u] for u in u_of]
        self._fts: List[np.ndarray] = [fts_u[u] for u in u_of]
        seg_ticks = np.array(sorted(seg_set), np.int64)
        n_seg = len(seg_ticks)
        seg_of_tick = (np.searchsorted(seg_ticks, np.arange(N), "right")
                       - 1).astype(np.int32)
        is_seg_start = np.zeros(N, bool)
        is_seg_start[seg_ticks] = True

        # drive the EngineOps adapter through every spec's events, once
        # uncapped and once capped, snapshotting planes per segment
        rate = np.zeros((n_seg, U, G), np.float32)
        cap = np.zeros((n_seg, U, G), np.int32)
        outage = np.zeros((n_seg, U), bool)
        floor = np.zeros((n_seg, U), np.float32)
        downscale = np.zeros((n_seg, U), np.int32)
        minq = np.zeros((n_seg, U), np.int32)
        n_unc = np.full((n_seg, U), -1, np.int32)
        n_cap = np.full((n_seg, U), -1, np.int32)
        origin_up = np.ones((n_seg, U, G), bool)
        dp_degrade_sbg = np.ones((n_seg, U, G))
        dp_flush_sbg = np.zeros((n_seg, U, G), bool)
        for u, ln in enumerate(reps):
            ops_u = JaxLaneOps(ln.spec, ln.pairs, budget_capped=False)
            ops_c = JaxLaneOps(ln.spec, ln.pairs, budget_capped=True)
            by_tick: Dict[int, list] = {}
            for (t, kind, arg), ft in zip(evs_u[u], fts_u[u]):
                if ft < N:
                    by_tick.setdefault(int(ft), []).append((kind, arg))
            for s, st in enumerate(seg_ticks):
                ops_u.scale_n = None
                ops_c.scale_n = None
                ops_u.flush_edge[:] = False
                for kind, arg in by_tick.get(int(st), []):
                    timeline_registry.apply_op(ops_u, kind, arg, 0.0)
                    timeline_registry.apply_op(ops_c, kind, arg, 0.0)
                rate[s, u] = ops_u.rate_h()
                cap[s, u] = ops_u.cap
                outage[s, u] = ops_u.outage
                floor[s, u] = ops_u.floor_fraction
                downscale[s, u] = ops_u.downscale_target
                minq[s, u] = ops_u.min_queue_eff
                origin_up[s, u] = ops_u.origin_up
                dp_degrade_sbg[s, u] = ops_u.dp_degrade
                dp_flush_sbg[s, u] = ops_u.flush_edge
                if ops_u.scale_n is not None:
                    n_unc[s, u] = ops_u.scale_n
                if ops_c.scale_n is not None:
                    n_cap[s, u] = ops_c.scale_n
        planes = {"rate": rate, "cap": cap, "outage": outage,
                  "floor": floor, "downscale": downscale,
                  "minq": minq, "n_unc": n_unc, "n_cap": n_cap}
        self.seg_of_tick = seg_of_tick
        self.is_seg_start = is_seg_start

        # count-plane geometry: W progress steps (one per dt until the
        # job wall), L checkpoint levels, and the per-spec maps between
        # them (requeue level of a step; queue-drain start step)
        lease = np.array([ln.spec.lease_interval_s for ln in reps])
        connected = lease[:, None] < g_nat[None, :]          # [U,G]
        nat_g = (~connected).astype(np.int32)
        self.nat_any = bool(nat_g.any())
        wall = np.array([ln.spec.job_wall_h for ln in reps])
        ckpt = np.array([ln.spec.job_checkpoint_h for ln in reps])
        self.L = L = max(1, int(np.max(np.floor(wall / ckpt)) + 1))
        wfin1 = np.maximum(
            0, np.ceil(wall / self.dt - 1e-9).astype(np.int64) - 1)
        self.W = W = int(wfin1.max()) + 1
        finmask = (np.arange(W)[None, :] >= wfin1[:, None]) \
            .astype(np.int32)                                # [U,W]
        lvl_of_w = np.minimum(np.floor(
            np.arange(W)[None, :] * self.dt / ckpt[:, None] + 1e-9)
            .astype(np.int64), L - 1)
        M_wl = np.zeros((U, W, L), np.float32)
        M_wl[np.arange(U)[:, None], np.arange(W)[None, :], lvl_of_w] = 1.0
        # queue drain order j: levels L-1..0 (highest checkpoint first),
        # then fresh (j = L) starting at step 0
        lev_of_j = np.concatenate([np.arange(L - 1, -1, -1), [0]])
        w0_of_j = np.minimum(np.rint(
            lev_of_j[None, :] * ckpt[:, None] / self.dt).astype(np.int64),
            W - 1)
        w0_of_j[:, L] = 0
        M_jw = np.zeros((U, L + 1, W), np.float32)
        M_jw[np.arange(U)[:, None], np.arange(L + 1)[None, :],
             w0_of_j] = 1.0
        lane_consts = {}

        # -- data plane: stage-in as a count-axis front extension.  A
        # matched job enters at ext position S_max + w0 - S and reaches
        # its old entry step after exactly S staging ticks (finish
        # thresholds shift by S_max, so stage + progress duration is
        # exact per job).  Killed staging cells requeue at the level of
        # their position past S_max — a statistical approximation (their
        # true pre-stage checkpoint level is not tracked per cell).
        dp = getattr(ref.spec, "dataplane", None)
        dp_size = float(getattr(ref.spec, "job_input_gb", 0.0))
        origins_g = [dp.origin_for(n) if dp is not None else None
                     for n in self.g_provider]
        self.dp_active = dp is not None and bool(dp.origins)
        self.dp_staging = self.dp_active and dp_size > 0.0
        self.dp_base_g = [n.split("/", 1)[0] for n in self.g_provider]
        dp_has_g = np.array([o is not None for o in origins_g],
                            np.float32)
        r_g = np.array([o.cache_hit_rate if o else 0.0
                        for o in origins_g], np.float32)
        usd_miss_g = np.array(
            [dp_size * o.egress_usd_per_gb if o else 0.0
             for o in origins_g], np.float32)
        if self.dp_staging:
            def _ticks(gbps):
                # vectorized dataplane.stage_ticks (0 where gbps <= 0)
                gbps = np.asarray(gbps, np.float64)
                hours = dp_size * 8.0 / np.where(gbps > 0.0, gbps, 1.0) \
                    / 3600.0
                t = np.maximum(1, np.ceil(hours / self.dt - 1e-9)
                               .astype(np.int64))
                return np.where(gbps > 0.0, t, 0)

            bw_g = np.array([o.bandwidth_gbps if o else 0.0
                             for o in origins_g])
            hbw_g = np.array(
                [(o.cache_bandwidth_gbps if o.cache_bandwidth_gbps > 0.0
                  else o.bandwidth_gbps) if o else 0.0
                 for o in origins_g])
            S_hit = _ticks(hbw_g)                            # [G]
            S_miss = _ticks(bw_g[None, None, :] * dp_degrade_sbg) \
                .astype(np.int32)                            # [S,U,G]
            S_max = int(max(S_hit.max(), S_miss.max()))
            W_ext = W + S_max
            finmask = (np.arange(W_ext)[None, :]
                       >= S_max + wfin1[:, None]).astype(np.int32)
            lvl_of_ext = np.minimum(np.floor(np.clip(
                np.arange(W_ext)[None, :] - S_max, 0, None)
                * self.dt / ckpt[:, None] + 1e-9)
                .astype(np.int64), L - 1)
            M_wl = np.zeros((U, W_ext, L), np.float32)
            M_wl[np.arange(U)[:, None], np.arange(W_ext)[None, :],
                 lvl_of_ext] = 1.0
            ui = np.arange(U)[:, None, None]
            gi = np.arange(G)[None, :, None]
            ji = np.arange(L + 1)[None, None, :]
            pos_hit = S_max + w0_of_j[:, None, :] \
                - S_hit[None, :, None]                       # [U,G,L+1]
            E_hit = np.zeros((U, G, L + 1, W_ext), np.float32)
            E_hit[ui, gi, ji, pos_hit] = 1.0
            E_miss = np.zeros((n_seg, U, G, L + 1, W_ext), np.float32)
            for s in range(n_seg):
                pos_miss = S_max + w0_of_j[:, None, :] \
                    - S_miss[s][:, :, None]
                E_miss[s][ui, gi, ji, pos_miss] = 1.0
            planes["S_miss"] = S_miss
            planes["E_miss"] = E_miss
            planes["dp_flush"] = dp_flush_sbg
            lane_consts["E_hit"] = E_hit
            # expected hit-credit loss when a pilot's rotation resets:
            # over n stage-ins the rotation yields floor(n*r) hits, a
            # deficit of frac(n*r) vs the accumulator's exact n*r —
            # averaged over lifetimes (numerically, any float r)
            n_ = np.arange(1, 201)[:, None]
            loss_g = np.where(
                r_g > 0.0,
                np.modf(n_ * r_g[None, :].astype(np.float64))[0].mean(0),
                0.0).astype(np.float32)
            dp_consts = {"dp_r_g": r_g, "dp_has_g": dp_has_g,
                         "dp_usd_miss_g": usd_miss_g, "dp_loss_g": loss_g,
                         "S_hit_g": S_hit.astype(np.float32)}
        else:
            dp_consts = {}
        if self.dp_active:
            planes["origin_up"] = origin_up
        self.planes = {k: at_lanes(v, 1) for k, v in planes.items()}
        lane_consts.update(
            nat_g=nat_g, finmask=finmask, M_wl=M_wl, M_jw=M_jw,
            overhead=np.array([ln.spec.overhead_per_day for ln in reps],
                              np.float32),
            budget=np.array([ln.spec.budget for ln in reps], np.float32))
        lc = {k: at_lanes(v, 0) for k, v in lane_consts.items()}
        if self.dp_staging:
            dp_consts["E_hit"] = lc["E_hit"]

        self.consts = {
            "prov_onehot": prov_onehot,
            "pre_rate_g": g_pre_rate,
            "pre_scale_g": g_pre_scale,
            "nat_g": lc["nat_g"],
            "finmask_rg": np.repeat(lc["finmask"], G, axis=0),  # [B*G,W]
            "M_wl": lc["M_wl"],
            "M_jw": lc["M_jw"],
            "overhead": lc["overhead"],
            "budget": lc["budget"],
            "dt": np.float32(self.dt),
            "seeds": np.array([ln.seed for ln in self.lanes], np.uint32),
            **dp_consts,
        }
        assert (self.consts["budget"] > 0).all(), \
            "sweep lanes need a budget"
        self.out: Optional[dict] = None
        # read-back state: the output columns and cap ticks of the last
        # run, and the replayed records by (spec row, cap tick)
        self._cols: Optional[Dict[str, list]] = None
        self._cap_ticks: Optional[List[int]] = None
        self._replays: Dict[Tuple[int, int], Tuple[List[dict], bool]] = {}

    def _scan_call(self):
        """The scan's one argument, packed and put on the device, and
        its static options."""
        groups = {"planes": self.planes, "consts": self.consts,
                  "xs": {"tick": np.arange(self.N, dtype=np.int32),
                         "seg": self.seg_of_tick,
                         "start": self.is_seg_start}}
        layout = _layout(groups)
        return (jnp.asarray(_pack(groups, layout)),), dict(
            layout=layout, nat_any=self.nat_any, use_pallas=self.use_pallas,
            dp_gating=self.dp_active, dp_staging=self.dp_staging)

    def lower(self):
        """The scan exactly as :meth:`run` dispatches it, lowered:
        ``.compile().as_text()`` is the program the device runs."""
        args, kw = self._scan_call()
        return _scan_campaigns.lower(*args, **kw)

    def run(self) -> "JaxSweepEngine":
        """Stages the arguments, dispatches the scan, waits for the
        device and copies the outputs back: one ``obs`` span each, one
        transfer each way."""
        with obs.span("engine.put"):
            args, kw = self._scan_call()
            staged = jax.tree.leaves(args)
            obs.count("h2d_bytes", sum(a.nbytes for a in staged))
            obs.count("h2d_arrays", len(staged))
        with obs.span("engine.launch"):
            out = _scan_campaigns(*args, **kw)
        with obs.span("engine.wait"):
            out = jax.block_until_ready(out)
        with obs.span("engine.fetch"):
            buf = np.asarray(out)
            layout = _out_layout(*_out_sizes(self.consts))
            self.out = _unpack(buf, layout)["out"]
            self._cols = self._cap_ticks = None
            obs.count("d2h_bytes", buf.nbytes)
            obs.count("d2h_arrays", 1)
        return self

    # -- per-lane provenance + results ------------------------------------
    def lane_events(self, b: int) -> List[dict]:
        """The lane's ``events_fired`` records, schema-identical to the
        solo and batched engines'.  They are a function of the lane's
        spec and of the tick at which the scan applied its budget cap
        alone, so the registry replay (:meth:`_replay`) runs once per
        distinct (spec row, cap tick) and each lane gets copies of the
        records that no other lane shares.  Counts ``replay_lanes`` and
        ``replay_keys`` (the replays run) on the open span."""
        if self._cap_ticks is None:
            self._cap_ticks = self.out["cap_tick"].tolist() \
                if self.out is not None else [-1] * self.B
        key = (self._row[b], self._cap_ticks[b])
        hit = self._replays.get(key)
        if hit is None:
            recs = self._replay(b, key[1])
            flat = all(isinstance(v, _ATOMS) for r in recs
                       for v in r.values())
            hit = self._replays[key] = (recs, flat)
            obs.count("replay_keys")
        obs.count("replay_lanes")
        recs, flat = hit
        return [dict(r) for r in recs] if flat else copy.deepcopy(recs)

    def _replay(self, b: int, cap_tick: int) -> List[dict]:
        """Reconstruct lane ``b``'s ``events_fired`` records through the
        registry's own ``apply_op`` bodies (the budget cap is inserted at
        ``cap_tick``, the tick the scan applied it; -1 if never)."""
        ln = self.lanes[b]
        ops = JaxLaneOps(ln.spec, ln.pairs)
        by_tick: Dict[int, list] = {}
        for (t, kind, arg), ft in zip(self._evs[b], self._fts[b]):
            if ft < self.N:
                by_tick.setdefault(int(ft), []).append((kind, arg))
        ticks = sorted(set(by_tick)
                       | ({cap_tick} if cap_tick >= 0 else set()))
        recs: List[dict] = []
        for ft in ticks:
            now = float(self.tick_times[ft])
            ops.budget_capped = 0 <= cap_tick <= ft
            if ft == cap_tick:
                recs.append(timeline_registry.apply_budget_cap(ops, now))
            for kind, arg in by_tick.get(ft, []):
                recs.append(timeline_registry.apply_op(ops, kind, arg,
                                                       now))
        return recs

    def _columns(self) -> Dict[str, list]:
        """The outputs :meth:`lane_results` reads, as Python lists, one
        ``tolist`` a field a run: float32 numbers become the Python
        floats, and int32 counts the ints, that reading them element by
        element gives.  Beside them: each lane's float32 row sum of
        ``egress_g``, whether any of its groups has egress, and its live
        instances summed per provider (exact integer sums)."""
        if self._cols is None:
            out = self.out
            assert out is not None, "run() first"
            cols = {k: out[k].tolist() for k in _RESULT_FIELDS}
            cols["budget"] = self.consts["budget"].tolist()
            cols["egress_sum"] = out["egress_g"].sum(axis=1).tolist()
            cols["egress_any"] = (out["egress_g"] > 0).any(axis=1).tolist()
            cols["live_p"] = (out["live_g"].astype(np.int64) @ self.consts[
                "prov_onehot"].astype(np.int64)).tolist()
            self._cols = cols
        return self._cols

    def lane_results(self, b: int) -> dict:
        """Summary totals, schema-identical to the other engines'
        ``results()`` (same keys, grouping and rounding)."""
        c = self._columns()
        sc = self.lanes[b].spec
        providers = self.providers
        busy_by_prov = {name: h for name, h in zip(providers,
                                                    c["busy_prov"][b])
                        if h > 0}
        busy_h = c["busy_h"][b]
        if self.homogeneous:
            eflop = busy_h * sc.accel_tflops * 1e12 / 1e18
        else:
            eflop = sum(
                h * (self.provider_tflops.get(name) or sc.accel_tflops)
                for name, h in busy_by_prov.items()) * 1e12 / 1e18
        spent = c["spent"][b]
        budget = c["budget"][b]
        raw_by_prov = {name: v for name, v in zip(providers, c["by_prov"][b])
                       if v > 0}
        # egress lands under the BASE provider name, merged before
        # rounding (same grouping as the other engines' ledgers)
        if c["egress_any"][b]:
            for base, e in zip(self.dp_base_g, c["egress_g"][b]):
                if e > 0:
                    raw_by_prov[base] = raw_by_prov.get(base, 0.0) + e
        ledger_by_prov = {k: round(v, 2) for k, v in raw_by_prov.items()}
        infra = c["infra"][b]
        if infra > 0:
            ledger_by_prov["infra"] = round(infra, 2)
        accel = c["accel"][b]
        accel_days = accel / 24.0
        cost = round(spent, 2)
        remaining = max(0.0, budget - spent)
        hits, misses = c["hits"][b], c["misses"][b]
        return {
            "accel_hours": round(accel, 1),
            "accel_days": round(accel_days, 1),
            "busy_hours": round(busy_h, 1),
            "busy_hours_by_provider": {
                k: round(v, 1) for k, v in sorted(busy_by_prov.items())},
            "eflop_hours_fp32": round(eflop, 3),
            "cost": cost,
            "cost_per_accel_day": round(spent / max(accel_days, 1e-9), 2),
            "preemptions": c["pre_ct"][b],
            "nat_drops": c["nat_ct"][b],
            "jobs_finished": c["fin_ct"][b],
            "egress_usd": round(c["egress_sum"][b], 2),
            "stagein_hours": round(c["stage_t"][b] * self.dt, 1),
            "cache_hit_fraction": round(hits / (hits + misses), 4)
            if hits + misses else 0.0,
            "budget": {
                "total_spent": cost,
                "by_provider": dict(sorted(ledger_by_prov.items())),
                "remaining": round(remaining, 2),
                "remaining_fraction": round(remaining / budget, 4),
                "overdraft": round(max(0.0, spent - budget), 2),
            },
            "by_provider": dict(zip(providers, c["live_p"][b])),
        }


def run_jax_detailed(lane_specs: Sequence[Tuple[CampaignSpec, int]],
                     use_pallas: Optional[bool] = None
                     ) -> List[Tuple[dict, List[dict], None]]:
    """Run every (spec, seed) lane on the compiled engine, batching by
    the same structural key as the numpy engine; returns per-lane
    ``(results, events_fired, None)`` in input order (the trace slot is
    always None — ``collect="trace"`` is a bit-identity surface the
    statistical engine does not implement)."""
    with obs.call("engine.call"):
        with obs.span("engine.prepare"):
            # preparing is a function of the spec alone: once per
            # distinct spec, and its other lanes share the result
            u_of, firsts = _distinct([sc for sc, _seed in lane_specs])
            prepared: List[Tuple[tuple, _Lane]] = []
            for i, ((sc, seed), u) in enumerate(zip(lane_specs, u_of)):
                if i == firsts[u]:
                    prepared.append(_prepare(sc, seed))
                else:
                    key, ln = prepared[firsts[u]]
                    prepared.append((key, _Lane(ln.spec, seed, ln.pairs)))
        batches: Dict[tuple, List[int]] = {}
        for i, (key, _lane) in enumerate(prepared):
            batches.setdefault(key, []).append(i)
        out: List[Optional[tuple]] = [None] * len(prepared)
        for idxs in batches.values():
            with obs.span("engine.bake"):
                eng = JaxSweepEngine([prepared[i][1] for i in idxs],
                                     use_pallas=use_pallas)
            with obs.span("engine.scan"):
                eng.run()
            lanes = range(len(idxs))
            with obs.span("engine.results"):
                results = [eng.lane_results(j) for j in lanes]
            with obs.span("engine.events"):
                events = [eng.lane_events(j) for j in lanes]
            for i, res, evs in zip(idxs, results, events):
                out[i] = (res, evs, None)
        return out


def run_jax(lane_specs: Sequence[Tuple[CampaignSpec, int]],
            use_pallas: Optional[bool] = None) -> List[dict]:
    """Like :func:`run_jax_detailed`, results only."""
    return [res for res, _events, _trace in
            run_jax_detailed(lane_specs, use_pallas=use_pallas)]
