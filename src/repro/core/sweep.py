"""Batched multi-campaign sweep engine: B campaigns as one array program.

A Monte-Carlo planning sweep (seeds x what-if scenarios, see
core/scenarios.py) used to run one Python tick loop per campaign, paying
the fixed per-tick dispatch overhead B times.  ``BatchedFleetEngine``
ticks B independent campaigns in lock-step instead: instances, pilots and
jobs of *all* lanes live in one flat struct-of-arrays with a
``lane*G + group`` column, so preemption sampling, billing, lease/NAT
checks, matchmaking and job progress are single vectorized ops across
every campaign at once.  Per-campaign job queues are lanes of one ring
buffer; per-campaign budgets are columns of one vectorized ledger.

Reproducibility is exact, not statistical: lane b draws from its own
``np.random.default_rng(seed_b)`` — the same generator a solo
``CloudSimulator`` would build — and consumes it in the same order
(preemption draws per group in price order, creation order within a
group; ``rng.random(k1); rng.random(k2)`` reads the PCG64 stream exactly
like ``rng.random(k1+k2)``).  Every lane therefore reports ``results()``
totals matching a solo ``spec.run_solo()`` at the same (seed, spec) —
pinned by tests/test_sweep.py, including the paper replay at seed 2021.

The hot loop never rescans or re-sorts the whole fleet: the engine
maintains an aliveness mask, per-(lane, group) live counts, a
lane-sorted row list (lazily compacted), and idle/busy pilot candidate
sets incrementally, so each tick touches O(rows that changed) plus a
handful of flat gathers.  Billing exploits lock-step: every billable row
accrues the same scalar interval, so a tick's charges are one bincount.

Lanes are grouped into lock-step batches by structural compatibility
(tick size, duration, and the price-ordered (provider, region) group
list); prices, budgets, timelines, lease intervals and queue depths
vary freely per lane within a batch.

Campaign control is the declarative ``CampaignSpec`` timeline
(core/spec.py): ``SetTarget`` / ``CEOutage`` / ``PriceShift`` /
``BudgetFloor`` / ``CapacityShift`` / ``PriceCurve`` events compile to
per-lane ``(t, kind, arg)`` tuples interpreted by ``_run_events`` — no
Python callbacks to special-case.  Effective billing rates follow the
engines' shared expression ``((base) * PriceShift scalar) * curve
factor``: the cumulative scalar is per-lane, the absolute curve factors
are per-(lane, group) (``curve_lg``), and both are only touched at
event time (``_refresh_rates``), so the hot loop never recomputes
prices.  Every executed event is recorded in a per-lane
``events_fired`` provenance log, bit-identical to the solo
``TimelineController``'s.

Tick-phase primitives (hazard model, checkpoint flooring, segmented
ranks) are shared with the solo array engine — see core/fleet.py.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import timeline as timeline_registry
from repro.core.budget import BudgetLedger
from repro.core.events import CampaignTrace, TraceRecorder, build_trace
from repro.core.fleet import (_NO_PILOT, _PILOT_DEAD, _PILOT_LIVE,
                              checkpoint_floor, preemption_rate,
                              segment_ranks)
from repro.core.spec import CampaignSpec, build_catalog

# ledger alert levels, descending — the solo controller reacts to these
# ledger callbacks, so both engines must cross the same set
_THRESHOLDS = tuple(sorted(
    BudgetLedger.__dataclass_fields__["thresholds"].default, reverse=True))


def _sorted_insert(a: np.ndarray, vs: np.ndarray) -> np.ndarray:
    """Merge sorted values ``vs`` into sorted array ``a`` in one pass
    (np.insert takes a slower generic path)."""
    if not len(vs):
        return a
    at = np.searchsorted(a, vs) + np.arange(len(vs))
    out = np.empty(len(a) + len(vs), dtype=a.dtype)
    mask = np.ones(len(out), dtype=bool)
    mask[at] = False
    out[at] = vs
    out[mask] = a
    return out


def _sorted_remove(a: np.ndarray, vs: np.ndarray) -> np.ndarray:
    """Remove present sorted values ``vs`` from sorted array ``a``."""
    if not len(vs):
        return a
    mask = np.ones(len(a), dtype=bool)
    mask[np.searchsorted(a, vs)] = False
    return a[mask]


@dataclass
class _Lane:
    """One (spec, seed) campaign prepared for batching."""
    spec: CampaignSpec
    seed: int
    pairs: list          # (ProviderSpec, RegionSpec), price-ordered


def _compile_timeline(spec: CampaignSpec) -> List[tuple]:
    """Flatten a spec's event timeline into stably time-sorted
    ``(t, op_kind, arg)`` tuples — registry-derived
    (``timeline.compile_timeline``), so the expansion order (CEOutage
    becomes on/off at its declaration point) and tie-breaking (stable by
    timeline position) are by construction the same one-shots the solo
    ``TimelineController`` installs."""
    return timeline_registry.compile_timeline(spec.timeline)


class _LaneOps:
    """One lane's :class:`~repro.core.timeline.EngineOps` adapter: the
    registry's shared ``apply`` bodies drive this to mutate lane ``b``'s
    slice of the struct-of-arrays state.  Each method mirrors the solo
    facade op exactly (same float-op order — see ``_refresh_rates``), so
    every lane stays bit-identical to a solo run."""

    __slots__ = ("eng", "b", "now")

    def __init__(self, eng: "BatchedFleetEngine", b: int, now: float):
        self.eng = eng
        self.b = b
        self.now = now

    @property
    def budget_capped(self) -> bool:
        return bool(self.eng.capped[self.b])

    @property
    def downscale_target(self) -> int:
        return int(self.eng.lane_downscale[self.b])

    def scale_to(self, n: int):
        self.eng._lane_scale_to(self.b, int(n), self.now)

    def deprovision_all(self):
        self.eng._lane_deprovision(self.b, self.now)

    def set_outage(self, on: bool):
        self.eng.outage[self.b] = bool(on)

    def scale_prices(self, factor: float):
        # cumulative per-lane scale on top of which curve factors stack
        # (solo: prov.scale_prices)
        self.eng.lane_price_scale[self.b] *= factor
        self.eng._refresh_rates(self.b)

    def set_price_factor(self, provider, factor: float):
        eng, b = self.eng, self.b
        if provider is None:
            eng.curve_lg[b * eng.G:(b + 1) * eng.G] = factor
        else:
            gs = eng._prov_groups.get(provider)
            if gs is not None:           # unknown provider: no-op (solo
                eng.curve_lg[b * eng.G + gs] = factor   # semantics)
        eng._refresh_rates(b)

    def scale_capacity(self, factor: float):
        eng, b = self.eng, self.b
        s = slice(b * eng.G, (b + 1) * eng.G)
        eng.g_cap_lg[s] = np.maximum(
            1, (eng.g_cap_lg[s] * factor).astype(np.int64))

    def arm_budget_floor(self, fraction: float, target: int):
        self.eng.lane_floor[self.b] = fraction
        self.eng.lane_downscale[self.b] = target

    def set_workload_factor(self, factor: float):
        eng, b = self.eng, self.b
        eng.lane_workload[b] = factor
        # cached at event time; int(int64 * float) is the same IEEE
        # product + truncation the solo sim computes per tick
        eng.lane_min_queue_eff[b] = int(eng.lane_min_queue[b] * factor)

    # -- data-plane ops (spec.OriginOutage/OriginDegrade/CacheFlush);
    #    keyed by base provider, exactly like DataPlaneRuntime ----------
    def set_origin_outage(self, provider: str, on: bool):
        eng, b = self.eng, self.b
        gs = eng._dp_groups_by_base.get(str(provider).split("/", 1)[0])
        if gs is not None:
            eng.stage_elig_lg[b * eng.G + gs] = not bool(on)

    def degrade_origin(self, provider: str, factor: float):
        eng, b = self.eng, self.b
        gs = eng._dp_groups_by_base.get(str(provider).split("/", 1)[0])
        if gs is not None:
            eng.dp_degrade_lg[b * eng.G + gs] *= float(factor)

    def flush_cache(self, provider: str):
        eng, b = self.eng, self.b
        gs = eng._dp_groups_by_base.get(str(provider).split("/", 1)[0])
        if gs is not None:
            eng.dp_epoch_lg[b * eng.G + gs] += 1


def _prepare(sc, seed: int) -> Tuple[tuple, _Lane]:
    sc = sc.to_spec().validate()      # to_spec(): CampaignSpec's identity hook
    cat = build_catalog(sc)
    pairs = [(p, r) for p in cat.values() for r in p.regions]
    pairs.sort(key=lambda pr: (
        pr[0].spot_price_per_day if sc.spot else
        pr[0].ondemand_price_per_day, pr[0].name, pr[1].name))
    key = (sc.dt_h, sc.duration_h, tuple(
        (p.name, r.name, r.capacity, r.preempt_rate_per_hour,
         r.preempt_scale_at_full, p.nat_idle_timeout_s, p.fp32_tflops)
        for p, r in pairs),
        # stage geometry must be lane-identical: ticks-per-transfer and
        # the per-group origin config are batch-level constants (the
        # per-lane outage/degrade/epoch *state* still varies freely)
        getattr(sc, "job_input_gb", 0.0), getattr(sc, "dataplane", None))
    return key, _Lane(sc, seed, pairs)


class BatchedFleetEngine:
    """B lock-step campaigns in one struct-of-arrays control plane."""

    def __init__(self, lanes: Sequence[_Lane], collect: bool = False,
                 sinks=None):
        self.lanes = list(lanes)
        B = len(self.lanes)
        assert B > 0
        self.B = B
        # per-lane typed event recorders (events.TraceRecorder); RNG-free,
        # so collecting traces never changes any lane.  ``sinks`` swaps
        # them for streaming recorders (traceops.StreamingRecorder) that
        # flush bounded tick-windows instead of accumulating.
        self._streaming = sinks is not None
        if self._streaming:
            if len(sinks) != B:
                raise ValueError(f"need one sink per lane: got "
                                 f"{len(sinks)} sinks for {B} lanes")
            from repro.core.traceops import StreamingRecorder
            self.recorders: Optional[List[TraceRecorder]] = \
                [StreamingRecorder(s) for s in sinks]
        else:
            self.recorders = \
                [TraceRecorder() for _ in range(B)] if collect else None
        ref = self.lanes[0]
        pairs = ref.pairs
        G = len(pairs)
        self.G = G
        self.LG = B * G
        self.dt = ref.spec.dt_h
        self.duration = ref.spec.duration_h

        # -- static per-group config (identical across lanes by batch key)
        self.g_provider = [p.name for p, _ in pairs]
        self.g_region = [r.name for _, r in pairs]
        self.g_capacity = np.array([r.capacity for _, r in pairs],
                                   dtype=np.int64)
        self.g_pre_rate = np.array([r.preempt_rate_per_hour
                                    for _, r in pairs])
        self.g_pre_scale = np.array([r.preempt_scale_at_full
                                     for _, r in pairs])
        g_nat = np.array([p.nat_idle_timeout_s for p, _ in pairs])
        # provider name -> column (order of first appearance + "infra")
        self.providers: List[str] = []
        for name in self.g_provider:
            if name not in self.providers:
                self.providers.append(name)
        self.Pn = len(self.providers)
        self.infra_col = self.Pn
        pi = np.array([self.providers.index(n) for n in self.g_provider])
        self.prov_onehot = np.zeros((G, self.Pn))
        self.prov_onehot[np.arange(G), pi] = 1.0
        self.provider_tflops = {p.name: p.fp32_tflops for p, _r in pairs}
        self.homogeneous = all(t is None
                               for t in self.provider_tflops.values())

        # flattened [LG] views used on the hot path; capacity is per-lane
        # state (CapacityShift events mutate a lane's slice mid-run)
        self.g_cap_lg = np.tile(self.g_capacity, B)
        self.g_pre_rate_lg = np.tile(self.g_pre_rate, B)
        self.g_pre_scale_lg = np.tile(self.g_pre_scale, B)

        # -- per-lane config columns -------------------------------------
        def col(f, dtype=np.float64):
            return np.array([f(ln.spec) for ln in self.lanes],
                            dtype=dtype)

        self.lane_budget = col(lambda s: s.budget)
        assert (self.lane_budget > 0).all(), "sweep lanes need a budget"
        self.lane_floor = col(lambda s: s.budget_floor_fraction)
        self.lane_downscale = col(lambda s: s.downscale_target, np.int64)
        self.lane_min_queue = col(lambda s: s.min_queue, np.int64)
        # request-rate factor (spec.WorkloadCurve) and the cached
        # effective top-up level it implies — refreshed at event time
        self.lane_workload = np.ones(B)
        self.lane_min_queue_eff = self.lane_min_queue.copy()
        self.lane_wall = col(lambda s: s.job_wall_h)
        self.lane_ckpt = col(lambda s: s.job_checkpoint_h)
        self.lane_overhead = col(lambda s: s.overhead_per_day)
        lease = col(lambda s: s.lease_interval_s)
        self.connected_lg = (lease[:, None] < g_nat[None, :]).ravel()
        self.nat_possible = not bool(self.connected_lg.all())
        # $/accel-hour per (lane, group): lane's spot/on-demand choice and
        # static price perturbation are baked into its built catalog;
        # PriceShift events multiply a per-lane cumulative scale on top
        # (effective = base * scale, the solo engines' exact expression)
        self._rate_base_lg = np.array(
            [((p.spot_price_per_day if ln.spec.spot
               else p.ondemand_price_per_day) / 24.0)
             for ln in self.lanes for p, _ in ln.pairs])
        self.rate_h_lg = self._rate_base_lg.copy()
        self.lane_price_scale = np.ones(B)
        # absolute per-(lane, group) curve factors (spec.PriceCurve);
        # group lists are identical across lanes (batch key), so one
        # name -> group-index map serves every lane
        self.curve_lg = np.ones(self.LG)
        self._prov_groups = {
            name: np.array([g for g, n in enumerate(self.g_provider)
                            if n == name], dtype=np.int64)
            for name in self.providers}

        # -- data plane (config is batch-identical by key; outage /
        #    degrade / epoch state varies per lane) ----------------------
        dp = getattr(ref.spec, "dataplane", None)
        self.dp_size = float(getattr(ref.spec, "job_input_gb", 0.0))
        self.dp_active = dp is not None and bool(dp.origins)
        self.dp_staging = self.dp_active and self.dp_size > 0.0
        base_g = [n.split("/", 1)[0] for n in self.g_provider]
        origins_g = [dp.origin_for(n) if dp is not None else None
                     for n in self.g_provider]
        self.dp_has_g = np.array([o is not None for o in origins_g])
        self.dp_rate_g = np.array([o.cache_hit_rate if o else 0.0
                                   for o in origins_g])
        self.dp_bw_g = np.array([o.bandwidth_gbps if o else 0.0
                                 for o in origins_g])
        self.dp_cbw_g = np.array([o.cache_bandwidth_gbps if o else 0.0
                                  for o in origins_g])
        # egress meters by BASE provider (sliced pools share their base's
        # origin), drained in sorted-name order like DataPlaneRuntime.bill
        self.dp_base_names = sorted(
            {base_g[g] for g in range(G) if origins_g[g] is not None})
        nb = max(1, len(self.dp_base_names))
        self.dp_price_base = np.array(
            [dp.origin_for(nm).egress_usd_per_gb
             for nm in self.dp_base_names]) if self.dp_base_names \
            else np.zeros(0)
        self.dp_baseidx_g = np.array(
            [self.dp_base_names.index(base_g[g])
             if origins_g[g] is not None else -1 for g in range(G)],
            dtype=np.int64)
        self._dp_groups_by_base = {}
        for g, bg in enumerate(base_g):
            self._dp_groups_by_base.setdefault(bg, []).append(g)
        self._dp_groups_by_base = {k: np.array(v, dtype=np.int64)
                                   for k, v in
                                   self._dp_groups_by_base.items()}
        self.stage_elig_lg = np.ones(self.LG, dtype=bool)
        self.dp_degrade_lg = np.ones(self.LG)
        self.dp_epoch_lg = np.zeros(self.LG, dtype=np.int64)
        self.dp_pending = np.zeros((B, nb), dtype=np.int64)
        self.dp_spent_by_base = np.zeros((B, nb))
        self.dp_egress_usd = np.zeros(B)
        self.dp_hits = np.zeros(B, dtype=np.int64)
        self.dp_misses = np.zeros(B, dtype=np.int64)
        self.staged_l = np.zeros(B, dtype=np.int64)

        # -- per-lane RNG/counters/state ---------------------------------
        self.rngs = [np.random.default_rng(ln.seed) for ln in self.lanes]
        self.inst_ctr = np.zeros(B, dtype=np.int64)
        self.pilot_seq = np.zeros(B, dtype=np.int64)
        self.job_seq = np.zeros(B, dtype=np.int64)
        self.g_target = np.zeros((B, G), dtype=np.int64)
        self.outage = np.zeros(B, dtype=bool)
        self.capped = np.zeros(B, dtype=bool)
        self.cap_pending = np.zeros(B, dtype=bool)

        # controller events: the spec timeline compiled to (t, kind, arg)
        # tuples, stably time-sorted per lane; every execution is logged
        # to the lane's events_fired provenance
        self.events: List[List[tuple]] = []
        self.events_fired: List[List[dict]] = [[] for _ in range(B)]
        self.ev_ptr = [0] * B
        self.next_event_t = np.full(B, np.inf)
        for b, ln in enumerate(self.lanes):
            evs = _compile_timeline(ln.spec)
            self.events.append(evs)
            if evs:
                self.next_event_t[b] = evs[0][0]
        # scalar fast-path guards so the per-tick event check is two
        # float/bool compares instead of two array reductions
        self._next_wake = float(self.next_event_t.min())
        self._cap_pending_any = False

        # -- vectorized ledger + totals ----------------------------------
        self.spent = np.zeros(B)
        self.by_provider = np.zeros((B, self.Pn + 1))
        self.fired = np.zeros((B, len(_THRESHOLDS)), dtype=bool)
        self.preemptions = np.zeros(B, dtype=np.int64)
        self.nat_drops = np.zeros(B, dtype=np.int64)
        self.finished = np.zeros(B, dtype=np.int64)
        self.accel_hours = np.zeros(B)
        self.busy_hours = np.zeros(B)
        self.busy_hours_by_provider = np.zeros((B, self.Pn))
        self.retired_hours_lg = np.zeros(self.LG)
        self.retired_count = np.zeros(B, dtype=np.int64)

        # -- instance SoA -------------------------------------------------
        self.n = 0
        cap = 4096
        self.i_lg = np.zeros(cap, dtype=np.int32)
        self.i_id = np.zeros(cap, dtype=np.int64)
        self.i_start = np.zeros(cap)
        self.i_end = np.full(cap, np.nan)          # nan == dead marker
        self.i_preempted = np.zeros(cap, dtype=bool)
        self.i_pilot = np.zeros(cap, dtype=np.int8)
        self.i_pilot_order = np.zeros(cap, dtype=np.int32)
        self.i_job = np.full(cap, -1, dtype=np.int32)
        # the running job's progress/wall/id, cached on the instance row
        # at match time: job-array gathers are random-access while busy
        # rows are walked in sorted order — advancing progress here is
        # ~9x cheaper (written back to a job row on requeue, where the
        # checkpoint floor is applied).  In scheduled-completion mode
        # progress is (now - i_match_t) since i_done0; the walk mode
        # advances i_done in place.  i_gen guards stale finish buckets.
        self.i_done = np.zeros(cap)
        self.i_done0 = np.zeros(cap)
        self.i_match_t = np.zeros(cap)
        self.i_gen = np.zeros(cap, dtype=np.int32)
        self.i_wall = np.zeros(cap)
        self.i_jid = np.zeros(cap, dtype=np.int64)
        self.alive = np.zeros(cap, dtype=bool)
        # data-plane stage-in state per row: ticks left on the current
        # transfer, the pilot's cache-hit rotation counter, and the
        # CacheFlush epoch that counter belongs to
        self.i_stage = np.zeros(cap, dtype=np.int64)
        self.i_stage_k = np.zeros(cap, dtype=np.int64)
        self.i_stage_epoch = np.zeros(cap, dtype=np.int64)

        # -- incremental hot-loop state -----------------------------------
        # live instance count per (lane, group); the single source the
        # hazard model, maintain deficit and results all read
        self.live_lg = np.zeros(self.LG, dtype=np.int64)
        # all rows ever alive, sorted by (lane, group, creation); dead
        # entries are filtered lazily, insertions go to segment ends
        self._cand_rows = np.empty(0, dtype=np.int32)
        self._cand_lg = np.empty(0, dtype=np.int32)
        self._pending_rows: List[np.ndarray] = []   # created, to cand-merge
        self._fresh_rows: List[np.ndarray] = []     # created, to register
        self._stopped_rows: List[np.ndarray] = []   # event stops this tick
        self._cand_dirty = False       # event stops left stale entries
        self._idle_cand = np.empty(0, dtype=np.int32)   # pilots sans job
        # busy pilots as an exact, row-sorted set: matches insert, requeues
        # and finishes delete, so _advance walks it with no validity scan
        self._busy_cand = np.empty(0, dtype=np.int32)
        self._busy_lg = np.zeros(self.LG, dtype=np.int64)
        self._created_lg = np.zeros(self.LG, dtype=np.int64)  # this tick
        self._died_lg = np.zeros(self.LG, dtype=np.int64)     # this tick
        self._billed_to = 0.0
        self._dead_unreaped = 0        # O(1) compaction triggers
        self._jobs_dead = 0
        # hot-path scratch buffers (preemption draws and thresholds)
        self._draws = np.empty(4096)
        self._thresh = np.empty(4096)
        self._hitbuf = np.empty(4096, dtype=bool)

        # -- scheduled job completion --------------------------------------
        # Progress advances uniformly by dt, so a job's finish tick is
        # known at match time; bucketing rows by completion tick lets
        # _advance touch only the rows due now instead of walking every
        # busy pilot.  Valid whenever the tick walk is float-exact (dt and
        # all tick times exactly representable — any binary dt like 0.25)
        # and no lane can NAT-drop mid-flight; otherwise fall back to the
        # per-tick walk over the sorted busy set.
        t_probe = 0.0
        exact = True
        for _ in range(int(np.ceil(self.duration / self.dt)) + 2):
            nxt = t_probe + self.dt
            if nxt - t_probe != self.dt:
                exact = False
                break
            t_probe = nxt
        # stage-in delays a matched job's start, so completion ticks are
        # no longer known at match time — staging batches take the walk
        self.scheduled_completion = exact and not self.nat_possible \
            and not self.dp_staging
        self._tick_idx = 0
        self._fin_buckets: Dict[int, list] = {}

        # -- jobs: anonymous fresh pool + materialized requeued rows ------
        # A fresh queued job is interchangeable with any other fresh job
        # of its lane (same wall/checkpoint, zero progress), so the CE's
        # 4000-deep top-up queue is just a per-lane counter; job rows are
        # materialized only when a preempted job returns to the queue
        # with checkpointed progress.  Requeues always re-enter at the
        # FRONT and fresh jobs only append at the BACK, so "requeued ring
        # then fresh pool" preserves the solo FIFO order exactly.
        self.fresh_q = np.zeros(B, dtype=np.int64)     # queued fresh jobs
        self.fresh_matched = np.zeros(B, dtype=np.int64)
        self.jn = 0
        jcap = 1 << 12
        self.j_id = np.zeros(jcap, dtype=np.int64)
        self.j_wall = np.zeros(jcap)
        self.j_ckpt = np.zeros(jcap)
        self.j_done = np.zeros(jcap)
        self.j_attempts = np.zeros(jcap, dtype=np.int32)
        self.j_state = np.zeros(jcap, dtype=np.int8)   # 0 live, 1 finished
        self.q_cap = 1 << 12                           # requeued ring only
        self.q_ring = np.zeros((B, self.q_cap), dtype=np.int64)
        self.q_head = np.zeros(B, dtype=np.int64)      # raw; slots mod q_cap
        self.q_len = np.zeros(B, dtype=np.int64)

    # -- growth -----------------------------------------------------------
    def _grow_instances(self, extra: int):
        need = self.n + extra
        cap = len(self.i_id)
        if need <= cap:
            return
        new = max(need, cap * 2)
        for name, fill in (("i_lg", 0), ("i_id", 0), ("i_start", 0),
                           ("i_end", np.nan), ("i_preempted", False),
                           ("i_pilot", 0), ("i_pilot_order", 0),
                           ("i_job", -1), ("i_done", 0), ("i_done0", 0),
                           ("i_match_t", 0), ("i_gen", 0), ("i_wall", 0),
                           ("i_jid", 0), ("alive", False), ("i_stage", 0),
                           ("i_stage_k", 0), ("i_stage_epoch", 0)):
            a = getattr(self, name)
            out = np.full(new, fill, dtype=a.dtype)
            out[:self.n] = a[:self.n]
            setattr(self, name, out)

    def _grow_jobs(self, extra: int):
        need = self.jn + extra
        cap = len(self.j_id)
        if need <= cap:
            return
        new = max(need, cap * 2)
        for name in ("j_id", "j_wall", "j_ckpt", "j_done",
                     "j_attempts", "j_state"):
            a = getattr(self, name)
            out = np.zeros(new, dtype=a.dtype)
            out[:self.jn] = a[:self.jn]
            setattr(self, name, out)

    def _grow_queue(self, incoming: np.ndarray):
        need = int((self.q_len + incoming).max())
        if need <= self.q_cap:
            return
        new_cap = self.q_cap
        while new_cap < need:
            new_cap *= 2
        new_ring = np.zeros((self.B, new_cap), dtype=np.int64)
        total = int(self.q_len.sum())
        if total:
            lanes = np.repeat(np.arange(self.B), self.q_len)
            rank = segment_ranks(lanes, self.q_len)
            old = self.q_ring[lanes, (self.q_head[lanes] + rank)
                              % self.q_cap]
            new_ring[lanes, rank] = old
        self.q_ring = new_ring
        self.q_cap = new_cap
        self.q_head[:] = 0

    # -- instance creation ------------------------------------------------
    def _append_rows(self, lg: np.ndarray, lanes: np.ndarray,
                     per_lane: np.ndarray, now: float):
        """Append created rows (lane-major, group-ascending ``lg``) with
        per-lane sequential IDs — the solo engine's creation order."""
        total = len(lg)
        if total == 0:
            return
        self._grow_instances(total)
        s = slice(self.n, self.n + total)
        self.i_lg[s] = lg
        self.i_id[s] = self.inst_ctr[lanes] + segment_ranks(lanes, per_lane)
        self.inst_ctr += per_lane
        self.i_start[s] = now
        self.i_end[s] = np.nan
        self.i_preempted[s] = False
        self.i_pilot[s] = _NO_PILOT
        self.i_pilot_order[s] = 0
        self.i_job[s] = -1
        self.i_stage[s] = 0
        self.i_stage_k[s] = 0
        self.i_stage_epoch[s] = 0
        self.alive[s] = True
        rows = np.arange(self.n, self.n + total,
                         dtype=np.int32)
        self.n += total
        if self.recorders is not None:
            ids = self.i_id[s]
            for j in range(total):
                b, g = divmod(int(lg[j]), self.G)
                self.recorders[b].launched(now, ids[j], self.g_provider[g],
                                           self.g_region[g])
        bc = np.bincount(lg, minlength=self.LG)
        self.live_lg += bc
        self._created_lg += bc
        self._pending_rows.append(rows)
        self._fresh_rows.append(rows)

    def _append_single(self, b: int, g: int, k: int, now: float):
        if k <= 0:
            return
        lg = np.full(k, b * self.G + g, dtype=np.int64)
        per_lane = np.zeros(self.B, dtype=np.int64)
        per_lane[b] = k
        self._append_rows(lg, np.full(k, b, dtype=np.int64), per_lane, now)

    # -- lane-scalar control (event-time only, mirrors the solo engine) ---
    def _lane_set_group_target(self, b: int, g: int, n: int, now: float):
        self.g_target[b, g] = max(0, n)
        lg = b * self.G + g
        live = int(self.live_lg[lg])
        fillable = int(min(self.g_target[b, g], self.g_cap_lg[lg]))
        if live < fillable:
            self._append_single(b, g, fillable - live, now)
        elif live > self.g_target[b, g]:
            rows = np.nonzero(self.alive[:self.n]
                              & (self.i_lg[:self.n] == lg))[0]
            stop = rows[self.g_target[b, g]:]     # newest extras stop
            self.i_end[stop] = now                # stopped, not preempted
            self.alive[stop] = False
            if self.recorders is not None:
                for iid in self.i_id[stop]:
                    self.recorders[b].stopped(now, iid,
                                              self.g_provider[g],
                                              self.g_region[g])
            self.live_lg[lg] -= len(stop)
            self._died_lg[lg] += len(stop)
            self._dead_unreaped += len(stop)
            self._cand_dirty = True
            self._stopped_rows.append(stop)

    def _lane_scale_to(self, b: int, n: int, now: float):
        remaining = max(0, int(n))
        for g in range(self.G):
            want = min(remaining, int(self.g_cap_lg[b * self.G + g]))
            self._lane_set_group_target(b, g, want, now)
            remaining -= int(self.live_lg[b * self.G + g])

    def _lane_deprovision(self, b: int, now: float):
        for g in range(self.G):
            self._lane_set_group_target(b, g, 0, now)

    def _refresh_rates(self, b: int):
        """Effective $/h for lane b: ((base) * shift scalar) * curve —
        the same float-op order as the solo engines' rate expression,
        so billing stays bit-identical."""
        s = slice(b * self.G, (b + 1) * self.G)
        self.rate_h_lg[s] = self._rate_base_lg[s] \
            * self.lane_price_scale[b] * self.curve_lg[s]

    # -- controller events ------------------------------------------------
    def _run_events(self, now: float):
        if not self._cap_pending_any and now < self._next_wake:
            return
        apply_op = timeline_registry.apply_op
        for b in range(self.B):
            fired = self.events_fired[b]
            ops = None
            # the budget-floor cap was scheduled "at now" during the
            # previous tick's billing — it sorts before any event due
            # this tick, exactly like the solo sim.at(now, ...) insertion
            if self.cap_pending[b]:
                ops = _LaneOps(self, b, now)
                rec = timeline_registry.apply_budget_cap(ops, now)
                fired.append(rec)
                if self.recorders is not None:
                    self.recorders[b].timeline_fired(rec)
                self.cap_pending[b] = False
            evs = self.events[b]
            while self.ev_ptr[b] < len(evs) \
                    and evs[self.ev_ptr[b]][0] <= now:
                _t, op_kind, arg = evs[self.ev_ptr[b]]
                self.ev_ptr[b] += 1
                if ops is None:
                    ops = _LaneOps(self, b, now)
                rec = apply_op(ops, op_kind, arg, now)
                fired.append(rec)
                if self.recorders is not None:
                    self.recorders[b].timeline_fired(rec)
            self.next_event_t[b] = evs[self.ev_ptr[b]][0] \
                if self.ev_ptr[b] < len(evs) else np.inf
        self._next_wake = float(self.next_event_t.min())
        self._cap_pending_any = False

    # -- vectorized tick phases ------------------------------------------
    def _maintain(self, now: float):
        """Group mechanisms refill to min(target, capacity) — pure
        arithmetic on the maintained live counts, no fleet scan."""
        fillable = np.minimum(self.g_target.ravel(), self.g_cap_lg)
        new = np.where(self.live_lg < fillable,
                       fillable - self.live_lg, 0)
        total = int(new.sum())
        if total == 0:
            return
        lg = np.repeat(np.arange(self.LG), new)     # lane-major, group-asc
        per_lane = new.reshape(self.B, self.G).sum(axis=1)
        self._append_rows(lg, lg // self.G, per_lane, now)

    def _requeue_front(self, rows: np.ndarray, lanes: np.ndarray,
                       now: float):
        """Jobs of lost pilots return to the FRONT of their lane's queue,
        work floored to the last checkpoint.  ``rows`` must be in the
        solo engine's appendleft order per lane (so the final queue
        layout — reversed within the batch — matches exactly)."""
        jr = self.i_job[rows]
        has = jr != -1
        rows, lanes, jr = rows[has], lanes[has], jr[has]
        if not len(rows):
            return
        anon = jr < 0                   # fresh jobs: materialize on first
        k = int(anon.sum())             # preemption, with their identity
        if k:
            self._grow_jobs(k)
            s = slice(self.jn, self.jn + k)
            arows = rows[anon]
            self.j_id[s] = self.i_jid[arows]
            self.j_wall[s] = self.i_wall[arows]
            self.j_ckpt[s] = self.lane_ckpt[lanes[anon]]
            self.j_done[s] = 0.0
            self.j_attempts[s] = 1      # matched once, as an anonymous job
            self.j_state[s] = 0
            jr[anon] = np.arange(self.jn, self.jn + k)
            self.jn += k
        if self.scheduled_completion:
            # progress since match is (now - match time): the tick walk
            # is float-exact here, so this equals the solo accumulation
            prog = self.i_done0[rows] + (now - self.i_match_t[rows])
        else:
            prog = self.i_done[rows]
            self._busy_cand = _sorted_remove(self._busy_cand,
                                             np.sort(rows))
        self.j_done[jr] = checkpoint_floor(prog, self.j_ckpt[jr])
        self.i_stage[rows] = 0   # an abandoned transfer restarts on re-match
        self._busy_lg -= np.bincount(self.i_lg[rows], minlength=self.LG)
        counts = np.bincount(lanes, minlength=self.B)
        rank = segment_ranks(lanes, counts)
        self._grow_queue(counts)
        new_head = self.q_head - counts
        pos = counts[lanes] - 1 - rank              # appendleft == reversed
        self.q_ring[lanes, (new_head[lanes] + pos) % self.q_cap] = jr
        self.q_head = new_head
        self.q_len += counts
        self.i_job[rows] = -1
        self.preemptions += counts

    def _sync_pilots(self, now: float):
        """Register pilots on rows created this tick; reap pilots of rows
        stopped this tick.  Both sets are tracked as they happen, so this
        touches only the changed rows (preemption hits reap themselves in
        _sample_preemptions, mirroring the solo phase order)."""
        if self._fresh_rows:
            rows = np.concatenate(self._fresh_rows) \
                if len(self._fresh_rows) > 1 else self._fresh_rows[0]
            self._fresh_rows = []
            rows = rows[self.alive[rows]]           # stopped-same-tick
            if len(rows):
                lgv = self.i_lg[rows]
                order = np.lexsort((rows, lgv))     # (lane, group, row)
                rows = rows[order]
                lanes = lgv[order] // self.G
                counts = np.bincount(lanes, minlength=self.B)
                self.i_pilot_order[rows] = self.pilot_seq[lanes] \
                    + segment_ranks(lanes, counts)
                self.pilot_seq += counts
                self.i_pilot[rows] = _PILOT_LIVE
                if self.recorders is not None:
                    for row in rows.tolist():
                        b, g = divmod(int(self.i_lg[row]), self.G)
                        # 1-based registration order == the object CE's
                        # pilot-id numbering
                        self.recorders[b].pilot_registered(
                            now, self.i_pilot_order[row] + 1,
                            self.i_id[row], self.g_provider[g])
                self._idle_cand = np.concatenate([self._idle_cand, rows])
        if self._stopped_rows:
            rows = np.concatenate(self._stopped_rows) \
                if len(self._stopped_rows) > 1 else self._stopped_rows[0]
            self._stopped_rows = []
            rows = rows[self.i_pilot[rows] == _PILOT_LIVE]
            if len(rows):
                lanes = self.i_lg[rows] // self.G
                order = np.lexsort((self.i_pilot_order[rows], lanes))
                rows, lanes = rows[order], lanes[order]
                self._requeue_front(rows, lanes, now)
                self.i_pilot[rows] = _PILOT_DEAD

    def _flush_cand(self):
        """Merge rows created this tick into the lane-sorted row list
        (segment-end insertion keeps creation order within a group)."""
        if not self._pending_rows:
            return
        rows = np.concatenate(self._pending_rows) \
            if len(self._pending_rows) > 1 else self._pending_rows[0]
        self._pending_rows = []
        lgv = self.i_lg[rows]
        order = np.argsort(lgv, kind="stable")      # row idx asc within lg
        rows, lgv = rows[order], lgv[order]
        at = np.searchsorted(self._cand_lg, lgv, side="right") \
            + np.arange(len(lgv))
        total = len(self._cand_rows) + len(rows)
        mask = np.ones(total, dtype=bool)
        mask[at] = False
        nr = np.empty(total, dtype=np.int32)
        nl = np.empty(total, dtype=np.int32)
        nr[at] = rows
        nr[mask] = self._cand_rows
        nl[at] = lgv
        nl[mask] = self._cand_lg
        self._cand_rows, self._cand_lg = nr, nl

    def _sample_preemptions(self, now: float, dt: float):
        self._flush_cand()
        if self._cand_dirty:                  # event stops this tick
            m = self.alive[self._cand_rows]
            self._cand_rows = self._cand_rows[m]
            self._cand_lg = self._cand_lg[m]
            self._cand_dirty = False
        rows = self._cand_rows
        lgv = self._cand_lg
        if not len(rows):
            return
        if len(rows) != int(self.live_lg.sum()):       # cheap invariant
            raise AssertionError("live-count bookkeeping diverged")
        lane_counts = self.live_lg.reshape(self.B, self.G).sum(axis=1)
        # one stream read per lane, written straight into the shared draw
        # buffer, consumed in the solo order (groups by price, creation
        # order within a group)
        if len(self._draws) < len(rows):
            self._draws = np.empty(max(len(rows), 2 * len(self._draws)))
        draws = self._draws[:len(rows)]
        rngs = self.rngs
        ofs = 0
        for b, c in enumerate(lane_counts.tolist()):
            if c:
                rngs[b].random(out=draws[ofs:ofs + c])
                ofs += c
        rate = preemption_rate(self.g_pre_rate_lg, self.g_pre_scale_lg,
                               self.live_lg, self.g_cap_lg)
        if len(self._thresh) < len(rows):
            self._thresh = np.empty(max(len(rows), 2 * len(self._thresh)))
            self._hitbuf = np.empty(len(self._thresh), dtype=bool)
        thresh = self._thresh[:len(rows)]
        np.take(rate * dt, lgv, out=thresh)
        hit = self._hitbuf[:len(rows)]
        np.less(draws, thresh, out=hit)
        if not hit.any():
            return
        hits = rows[hit]
        hit_lg = lgv[hit]
        keep = ~hit
        self._cand_rows = rows[keep]
        self._cand_lg = lgv[keep]
        self.i_end[hits] = now
        self.i_preempted[hits] = True
        self.alive[hits] = False
        if self.recorders is not None:
            for row, lgj in zip(hits.tolist(), hit_lg.tolist()):
                b, g = divmod(int(lgj), self.G)
                self.recorders[b].preempted(now, self.i_id[row],
                                            self.g_provider[g],
                                            self.g_region[g])
        hit_bc = np.bincount(hit_lg, minlength=self.LG)
        self.live_lg -= hit_bc
        self._died_lg += hit_bc
        self._dead_unreaped += len(hits)
        live_pilot = self.i_pilot[hits] == _PILOT_LIVE
        piloted = hits[live_pilot]
        self._requeue_front(piloted, hit_lg[live_pilot] // self.G, now)
        self.i_pilot[piloted] = _PILOT_DEAD

    def _ensure_jobs(self):
        """Top the CE queue up to min_queue — pure counter arithmetic:
        fresh jobs stay anonymous until matched (IDs are the submission
        order, which FIFO matching preserves)."""
        need = np.maximum(0, self.lane_min_queue_eff
                          - (self.q_len + self.fresh_q))
        self.fresh_q += need
        self.job_seq += need

    def _match(self, now: float):
        """Hand queued jobs to idle pilots in pilot-registration order.
        The idle set is maintained incrementally (registrations, finished
        jobs, unmatched leftovers) and validated by a point lookup here."""
        cand = self._idle_cand
        if not len(cand):
            return
        ok = self.alive[cand] & (self.i_pilot[cand] == _PILOT_LIVE) \
            & (self.i_job[cand] < 0)
        rows = cand[ok]
        if not len(rows):
            self._idle_cand = rows
            return
        lanes = self.i_lg[rows] // self.G
        # single-key sort on (lane << 32 | pilot_order) beats a 2-key
        # lexsort; pilot_order is per-lane and < 2^31
        key = lanes.astype(np.int64) << 32
        key |= self.i_pilot_order[rows].astype(np.int64)
        order = np.argsort(key, kind="stable")
        rows, lanes = rows[order], lanes[order]
        hold = None
        if self.dp_active:
            # origin outage gates NEW matches; gated pilots stay in the
            # idle set (the solo engines skip them in pilot order)
            em = self.stage_elig_lg[self.i_lg[rows]]
            if not em.all():
                hold = rows[~em]
                rows, lanes = rows[em], lanes[em]
                if not len(rows):
                    self._idle_cand = hold
                    return
        counts = np.bincount(lanes, minlength=self.B)
        k = np.minimum(counts, self.q_len + self.fresh_q)
        k[self.outage] = 0
        k1 = np.minimum(k, self.q_len)      # requeued ring drains first
        rank = segment_ranks(lanes, counts)
        sel = rank < k[lanes]
        ring_sel = rank < k1[lanes]
        mrows = rows[sel]
        r1 = rows[ring_sel]
        if len(r1):
            l1 = lanes[ring_sel]
            jobs = self.q_ring[l1, (self.q_head[l1] + rank[ring_sel])
                               % self.q_cap]
            self.i_job[r1] = jobs
            self.i_done0[r1] = self.j_done[jobs]
            self.i_wall[r1] = self.j_wall[jobs]
            self.i_jid[r1] = self.j_id[jobs]
            self.j_attempts[jobs] += 1
        self.q_head += k1
        self.q_len -= k1
        fresh_sel = sel & ~ring_sel
        r2 = rows[fresh_sel]
        if len(r2):
            l2 = lanes[fresh_sel]
            self.i_job[r2] = -2             # anonymous fresh job
            self.i_done0[r2] = 0.0
            self.i_wall[r2] = self.lane_wall[l2]
            self.i_jid[r2] = self.fresh_matched[l2] + 1 \
                + rank[fresh_sel] - k1[l2]
        k2 = k - k1
        self.fresh_matched += k2
        self.fresh_q -= k2
        self._busy_lg += np.bincount(self.i_lg[mrows], minlength=self.LG)
        self._idle_cand = rows[~sel] if hold is None \
            else np.concatenate([rows[~sel], hold])
        self.i_match_t[mrows] = now
        if self.dp_staging and len(mrows):
            self._stage_matches(mrows, now)
        if self.scheduled_completion:
            self._schedule_finish(mrows)
        else:
            self.i_done[mrows] = self.i_done0[mrows]
            self._busy_cand = _sorted_insert(self._busy_cand,
                                             np.sort(mrows))

    def _stage_matches(self, mrows: np.ndarray, now: float):
        """Vectorized ``DataPlaneRuntime.decide`` for this tick's
        matches: per-pilot cache-hit rotation, stage length in whole
        ticks, and per-(lane, base) egress-miss metering — the same
        scalar float expressions as core/dataplane.py, elementwise."""
        lgm = self.i_lg[mrows]
        g = lgm % self.G
        has = self.dp_has_g[g]          # groups without an origin: no-op
        if not has.any():
            return
        rws = mrows[has]
        lgs = lgm[has]
        gs = g[has]
        bs = lgs // self.G
        ep = self.dp_epoch_lg[lgs]
        reset = self.i_stage_epoch[rws] != ep     # CacheFlush: k resets
        if reset.any():
            self.i_stage_k[rws[reset]] = 0
            self.i_stage_epoch[rws[reset]] = ep[reset]
        k = self.i_stage_k[rws].astype(np.float64)
        r = self.dp_rate_g[gs]
        # int((k+1)*r) > int(k*r) with k, r >= 0: floor == trunc
        hit = np.floor((k + 1.0) * r) > np.floor(k * r)
        self.i_stage_k[rws] += 1
        gbps = np.where(hit,
                        np.where(self.dp_cbw_g[gs] > 0.0,
                                 self.dp_cbw_g[gs], self.dp_bw_g[gs]),
                        self.dp_bw_g[gs] * self.dp_degrade_lg[lgs])
        # stage_ticks(): 0 when gbps <= 0 (a fully-degraded origin)
        hours = self.dp_size * 8.0 / np.where(gbps > 0.0, gbps, 1.0) \
            / 3600.0
        ticks = np.where(
            gbps > 0.0,
            np.maximum(1, np.ceil(hours / self.dt - 1e-9)
                       .astype(np.int64)), 0)
        self.i_stage[rws] = ticks
        self.dp_hits += np.bincount(bs[hit], minlength=self.B)
        miss = ~hit
        self.dp_misses += np.bincount(bs[miss], minlength=self.B)
        np.add.at(self.dp_pending,
                  (bs[miss], self.dp_baseidx_g[gs[miss]]), 1)
        if self.recorders is not None:
            hitl = hit.tolist()
            tickl = ticks.tolist()
            for j, row in enumerate(rws.tolist()):
                if tickl[j] > 0:      # zero-tick stages are not events
                    self.recorders[int(bs[j])].stagein_started(
                        now, self.i_pilot_order[row] + 1, self.dp_size,
                        hitl[j], self.g_provider[int(gs[j])])

    def _schedule_finish(self, mrows: np.ndarray):
        """Bucket matched rows by their (known) completion tick.  The
        floor+correction computes the smallest m with done0 + m*dt >=
        wall using the exact product, so it lands on the same tick as
        the solo engine's accumulate-and-compare."""
        done0 = self.i_done0[mrows]
        wall = self.i_wall[mrows]
        m = np.floor((wall - done0) / self.dt)
        m += (done0 + m * self.dt) < wall
        m += (done0 + m * self.dt) < wall
        f = self._tick_idx + m.astype(np.int64) - 1
        gen = self.i_gen[mrows] + 1
        self.i_gen[mrows] = gen
        for fv in np.unique(f):
            msk = f == fv
            self._fin_buckets.setdefault(int(fv), []).append(
                (mrows[msk], gen[msk]))

    def _advance(self, dt: float, now: float):
        if self.scheduled_completion:
            bucket = self._fin_buckets.pop(self._tick_idx, None)
            if bucket is None:
                return
            if len(bucket) > 1:
                rows = np.concatenate([r for r, _ in bucket])
                gens = np.concatenate([g for _, g in bucket])
            else:
                rows, gens = bucket[0]
            # stale entries: requeued (i_job cleared) or re-matched
            # (generation bumped) since this bucket was scheduled
            valid = (self.i_gen[rows] == gens) & (self.i_job[rows] != -1)
            done_rows = rows[valid]
            if len(done_rows):
                self._finish_rows(done_rows, now)
            return
        self._advance_walk(dt, now)

    def _finish_rows(self, done_rows: np.ndarray, now: float):
        done_jobs = self.i_job[done_rows]
        done_lg = np.bincount(self.i_lg[done_rows], minlength=self.LG)
        self._busy_lg -= done_lg
        self.finished += done_lg.reshape(self.B, self.G).sum(axis=1)
        if self.recorders is not None:
            for row in done_rows.tolist():
                b = int(self.i_lg[row]) // self.G
                jrow = int(self.i_job[row])
                # anonymous fresh jobs (-2) were matched exactly once
                attempts = self.j_attempts[jrow] if jrow >= 0 else 1
                self.recorders[b].job_finished(now, self.i_jid[row],
                                               attempts)
        mat = done_jobs >= 0                   # anonymous jobs have no row
        if mat.any():
            dj = done_jobs[mat]
            self.j_state[dj] = 1
            self._jobs_dead += len(dj)
        self.i_job[done_rows] = -1
        self._idle_cand = np.concatenate([self._idle_cand, done_rows])

    def _advance_walk(self, dt: float, now: float):
        """Per-tick walk over the sorted busy set — the fallback for NAT
        batches (mid-flight drops) and non-binary tick sizes."""
        if self.nat_possible and len(self._busy_cand):
            rows = self._busy_cand
            lgv = self.i_lg[rows]
            dropped = ~self.connected_lg[lgv]
            if dropped.any():
                drop = rows[dropped]
                lanes = lgv[dropped] // self.G
                order = np.lexsort((self.i_pilot_order[drop], lanes))
                drop, lanes = drop[order], lanes[order]
                self.nat_drops += np.bincount(lanes, minlength=self.B)
                if self.recorders is not None:
                    for row in drop.tolist():
                        b, g = divmod(int(self.i_lg[row]), self.G)
                        self.recorders[b].nat_drop(
                            now, self.i_pilot_order[row] + 1,
                            self.i_id[row], self.g_provider[g])
                self._requeue_front(drop, lanes, now)  # deletes from busy
                self.i_pilot[drop] = _PILOT_DEAD
        rows = self._busy_cand
        if not len(rows):
            return
        if len(rows) != int(self._busy_lg.sum()):     # cheap invariant
            raise AssertionError("busy-count bookkeeping diverged")
        prows = rows
        if self.dp_staging:
            # stage-in burns the tick; the job progresses from the next
            staging = self.i_stage[rows] > 0
            if staging.any():
                srows = rows[staging]
                self.i_stage[srows] -= 1
                self.staged_l += np.bincount(
                    self.i_lg[srows] // self.G, minlength=self.B)
                if self.recorders is not None:
                    done_s = srows[self.i_stage[srows] == 0]
                    if len(done_s):
                        lanes = self.i_lg[done_s] // self.G
                        order = np.lexsort(
                            (self.i_pilot_order[done_s], lanes))
                        for row in done_s[order].tolist():
                            b = int(self.i_lg[row]) // self.G
                            self.recorders[b].stagein_finished(
                                now, self.i_pilot_order[row] + 1)
                prows = rows[~staging]
                if not len(prows):
                    return
        done = self.i_done[prows] + dt
        self.i_done[prows] = done
        fin = done >= self.i_wall[prows]
        if fin.any():
            self._finish_rows(prows[fin], now)
            # staging rows must stay busy: remove only the finished rows
            self._busy_cand = _sorted_remove(rows, prows[fin])

    def _bill(self, now: float):
        """Lock-step billing: every billable row accrued the same scalar
        interval since the last charge (rows created at ``now`` have
        nothing billable yet; rows that died this tick died at ``now``
        and owe the full interval), so a tick's charges are pure counter
        arithmetic — no fleet scan at all."""
        dh = now - self._billed_to
        if dh > 0:
            counts = self.live_lg + self._died_lg - self._created_lg
            amt_bg = (counts * dh * self.rate_h_lg).reshape(self.B, self.G)
            self.by_provider[:, :self.Pn] += amt_bg @ self.prov_onehot
            self.spent += amt_bg.sum(axis=1)
        if self.dp_active and self.dp_pending.any():
            # drain this tick's cache-miss egress right after the
            # GPU-hour charges, per base provider in sorted-name order —
            # the solo DataPlaneRuntime.bill contract, vectorized
            for j, base in enumerate(self.dp_base_names):
                cnt = self.dp_pending[:, j]
                if not cnt.any():
                    continue
                gb = self.dp_size * cnt
                usd = gb * self.dp_price_base[j]
                self.dp_egress_usd += usd
                chg = usd > 0.0
                if chg.any():
                    self.spent += np.where(chg, usd, 0.0)
                    self.dp_spent_by_base[:, j] += np.where(chg, usd, 0.0)
                if self.recorders is not None:
                    for b in np.nonzero(cnt > 0)[0].tolist():
                        self.recorders[b].egress_billed(
                            now, base, float(gb[b]), float(usd[b]))
            self.dp_pending[:] = 0
        self._billed_to = now
        self._died_lg[:] = 0
        self._created_lg[:] = 0
        self._compact_instances()
        self._compact_jobs()

    def _compact_instances(self):
        # every dead row is fully billed once its death tick's _bill ran
        # (this runs right after the charge step), so dead == compactable;
        # the running death counter makes the trigger O(1) per tick
        if self._dead_unreaped < 4096 or self._dead_unreaped * 4 < self.n:
            return
        dead = ~self.alive[:self.n] \
            & (self.i_pilot[:self.n] != _PILOT_LIVE)
        self._dead_unreaped = 0
        rows = np.nonzero(dead)[0]
        self.retired_hours_lg += np.bincount(
            self.i_lg[rows], minlength=self.LG,
            weights=self.i_end[rows] - self.i_start[rows])
        self.retired_count += np.bincount(
            self.i_lg[rows].astype(np.int64) // self.G, minlength=self.B)
        keep = np.nonzero(~dead)[0]
        newidx = np.full(self.n, -1, dtype=np.int32)
        newidx[keep] = np.arange(len(keep), dtype=np.int32)
        for name in ("i_lg", "i_id", "i_start", "i_end", "i_preempted",
                     "i_pilot", "i_pilot_order", "i_job", "i_done",
                     "i_done0", "i_match_t", "i_gen", "i_wall", "i_jid",
                     "alive", "i_stage", "i_stage_k", "i_stage_epoch"):
            arr = getattr(self, name)
            arr[:len(keep)] = arr[keep]
        self.n = len(keep)
        # remap candidate sets (drop stale dead entries first; remapping
        # is monotone, so lane-sorted order is preserved)
        m = newidx[self._cand_rows] >= 0
        self._cand_rows = newidx[self._cand_rows[m]]
        self._cand_lg = self._cand_lg[m]
        for attr in ("_idle_cand", "_busy_cand"):
            c = getattr(self, attr)
            nc = newidx[c]
            setattr(self, attr, nc[nc >= 0])
        # pending finish buckets hold row indices too; preempted entries
        # map to -1 and drop (their generation guard is then moot)
        for fv, lst in self._fin_buckets.items():
            newlst = []
            for r, g in lst:
                nr = newidx[r]
                mm = nr >= 0
                newlst.append((nr[mm], g[mm]))
            self._fin_buckets[fv] = newlst

    def _compact_jobs(self):
        """Finished materialized (once-requeued) jobs are dead weight;
        drop them and remap the row indices held by pilots and queues."""
        if self.jn < (1 << 14) or self._jobs_dead * 2 < self.jn:
            return
        dead = self.j_state[:self.jn] == 1
        self._jobs_dead = 0
        keep = np.nonzero(~dead)[0]
        newidx = np.full(self.jn, -1, dtype=np.int64)
        newidx[keep] = np.arange(len(keep))
        ij = self.i_job[:self.n]
        ref = ij >= 0
        ij[ref] = newidx[ij[ref]]
        total_q = int(self.q_len.sum())
        if total_q:
            lanes = np.repeat(np.arange(self.B), self.q_len)
            rank = segment_ranks(lanes, self.q_len)
            slot = (self.q_head[lanes] + rank) % self.q_cap
            self.q_ring[lanes, slot] = newidx[self.q_ring[lanes, slot]]
        for name in ("j_id", "j_wall", "j_ckpt", "j_done",
                     "j_attempts", "j_state"):
            arr = getattr(self, name)
            arr[:len(keep)] = arr[keep]
        self.jn = len(keep)

    def _charge_overhead(self, dt: float):
        amt = self.lane_overhead * dt / 24.0
        chg = amt > 0
        if chg.any():
            self.by_provider[chg, self.infra_col] += amt[chg]
            self.spent += np.where(chg, amt, 0.0)

    def _check_thresholds(self, now: float):
        """End-of-tick sweep over the ledger alert levels.  The solo
        ledger fires mid-charge, but every response is scheduled
        ``at(now)`` and so lands at the next tick either way; checking
        once after all of a tick's charges crosses the same levels."""
        frac = np.maximum(0.0, self.lane_budget - self.spent) \
            / self.lane_budget
        newly = np.zeros(self.B, dtype=bool)
        for i, th in enumerate(_THRESHOLDS):
            cross = (frac <= th) & ~self.fired[:, i]
            self.fired[:, i] |= cross
            newly |= cross
        trigger = newly & (frac <= self.lane_floor) & ~self.capped
        if trigger.any():
            self.capped |= trigger
            self.cap_pending |= trigger
            self._cap_pending_any = True

    def _accumulate(self, dt: float):
        running = self.live_lg.reshape(self.B, self.G).sum(axis=1)
        busy_bg = self._busy_lg.reshape(self.B, self.G)
        self.accel_hours += running * dt
        self.busy_hours += busy_bg.sum(axis=1) * dt
        self.busy_hours_by_provider += (busy_bg @ self.prov_onehot) * dt

    # -- the lock-step driver --------------------------------------------
    def tick(self, now: float, dt: float):
        self._run_events(now)
        self._maintain(now)
        self._sync_pilots(now)
        self._sample_preemptions(now, dt)
        self._sync_pilots(now)       # solo phase order (no-op here: both
        #                              death paths reap where they happen)
        self._ensure_jobs()
        self._match(now)
        self._advance(dt, now)
        self._bill(now)
        self._charge_overhead(dt)
        self._check_thresholds(now)
        self._accumulate(dt)

    def run(self) -> "BatchedFleetEngine":
        now = 0.0
        while now < self.duration:        # same float walk as the solo sim
            self.tick(now, self.dt)
            self._tick_idx += 1
            now += self.dt
        self._bill(now)                   # settle the final interval
        self.now = now
        return self

    # -- conservation view (tests) ---------------------------------------
    def billed_hours_by_lg(self) -> np.ndarray:
        out = self.retired_hours_lg.copy()
        end = np.where(self.alive[:self.n], self._billed_to,
                       self.i_end[:self.n])
        out += np.bincount(self.i_lg[:self.n], minlength=self.LG,
                           weights=np.maximum(
                               0.0, end - self.i_start[:self.n]))
        return out

    # -- per-lane results, schema-identical to CloudSimulator.results() --
    def lane_events(self, b: int) -> List[dict]:
        """The lane's executed-event provenance (timeline events plus
        budget-floor caps), bit-identical to the solo controller's
        ``events_fired``."""
        return list(self.events_fired[b])

    def lane_trace(self, b: int) -> Optional[CampaignTrace]:
        """The lane's typed event trace (``collect`` engines only) —
        byte-identical to the solo engines' trace at the same
        (spec, seed).  Streaming lanes fed their events to a sink and
        hold nothing to build from."""
        if self.recorders is None or self._streaming:
            return None
        ln = self.lanes[b]
        return build_trace(ln.spec.name, ln.seed, self.duration, self.dt,
                           self.recorders[b], self.events_fired[b])

    def lane_results(self, b: int) -> dict:
        sc = self.lanes[b].spec
        busy_by_prov = {}
        for pidx, name in enumerate(self.providers):
            h = float(self.busy_hours_by_provider[b, pidx])
            if h > 0:
                busy_by_prov[name] = h
        if self.homogeneous:
            eflop = float(self.busy_hours[b]) * sc.accel_tflops * 1e12 / 1e18
        else:
            eflop = sum(
                h * (self.provider_tflops.get(name) or sc.accel_tflops)
                for name, h in busy_by_prov.items()) * 1e12 / 1e18
        spent = float(self.spent[b])
        budget = float(self.lane_budget[b])
        raw_by_prov: Dict[str, float] = {}
        for pidx, name in enumerate(self.providers + ["infra"]):
            v = float(self.by_provider[b, pidx])
            if v > 0:
                raw_by_prov[name] = v
        # egress lands under the BASE provider name, merged before
        # rounding — matching the solo ledger's per-provider totals
        for j, base in enumerate(self.dp_base_names):
            e = float(self.dp_spent_by_base[b, j])
            if e > 0:
                raw_by_prov[base] = raw_by_prov.get(base, 0.0) + e
        ledger_by_prov = {k: round(v, 2) for k, v in raw_by_prov.items()}
        running = self.live_lg.reshape(self.B, self.G)[b]
        by_provider: Dict[str, int] = {}
        for g, name in enumerate(self.g_provider):
            by_provider[name] = by_provider.get(name, 0) + int(running[g])
        accel = float(self.accel_hours[b])
        return {
            "accel_hours": round(accel, 1),
            "accel_days": round(accel / 24.0, 1),
            "busy_hours": round(float(self.busy_hours[b]), 1),
            "busy_hours_by_provider": {
                k: round(v, 1) for k, v in sorted(busy_by_prov.items())},
            "eflop_hours_fp32": round(eflop, 3),
            "cost": round(spent, 2),
            "cost_per_accel_day": round(
                spent / max(accel / 24.0, 1e-9), 2),
            "preemptions": int(self.preemptions[b]),
            "nat_drops": int(self.nat_drops[b]),
            "jobs_finished": int(self.finished[b]),
            "egress_usd": round(float(self.dp_egress_usd[b]), 2),
            "stagein_hours": round(int(self.staged_l[b]) * self.dt, 1),
            "cache_hit_fraction": round(
                int(self.dp_hits[b])
                / (int(self.dp_hits[b]) + int(self.dp_misses[b])), 4)
            if int(self.dp_hits[b]) + int(self.dp_misses[b]) else 0.0,
            "budget": {
                "total_spent": round(spent, 2),
                "by_provider": dict(sorted(ledger_by_prov.items())),
                "remaining": round(max(0.0, budget - spent), 2),
                "remaining_fraction": round(
                    max(0.0, budget - spent) / budget, 4),
                "overdraft": round(max(0.0, spent - budget), 2),
            },
            "by_provider": by_provider,
        }


# lanes per engine: wider amortizes more Python dispatch, but the flat
# arrays must stay cache-resident — 64 paper-scale lanes (~130k
# instances, ~20 MB hot) is the empirical sweet spot on a laptop-class
# cache; chunking kicks in for wider sweeps
_MAX_LANES_PER_ENGINE = 64


def run_batched_detailed(lane_specs: Sequence[Tuple[CampaignSpec, int]],
                         max_lanes: int = _MAX_LANES_PER_ENGINE,
                         collect: str = "summary", sinks=None
                         ) -> List[Tuple[dict, List[dict],
                                         Optional[CampaignTrace]]]:
    """Run every (spec, seed) lane, batching lock-step-compatible lanes
    into shared engines (chunked to keep the working set in cache);
    returns per-lane ``(results, events_fired, trace)`` in input order
    (``trace`` is None unless ``collect="trace"``).  With
    ``collect="stream"`` each lane's canonical event stream goes to the
    matching entry of ``sinks`` (one traceops.TraceSink per lane, input
    order) instead of being held in memory — ``trace`` stays None."""
    if collect == "stream":
        if sinks is None or len(sinks) != len(lane_specs):
            raise ValueError(
                'collect="stream" needs sinks= with one '
                "traceops.TraceSink per lane")
    elif sinks is not None:
        raise ValueError('sinks= is only meaningful with '
                         'collect="stream"')
    prepared = [_prepare(sc, seed) for sc, seed in lane_specs]
    batches: Dict[tuple, List[int]] = {}
    for i, (key, _lane) in enumerate(prepared):
        batches.setdefault(key, []).append(i)
    out: List[Optional[tuple]] = [None] * len(prepared)
    for idxs in batches.values():
        for c in range(0, len(idxs), max_lanes):
            chunk = idxs[c:c + max_lanes]
            chunk_sinks = [sinks[i] for i in chunk] \
                if sinks is not None else None
            eng = BatchedFleetEngine([prepared[i][1] for i in chunk],
                                     collect=(collect == "trace"),
                                     sinks=chunk_sinks).run()
            if chunk_sinks is not None:
                for j in range(len(chunk)):
                    ln = eng.lanes[j]
                    eng.recorders[j].finish(ln.spec.name, ln.seed,
                                            eng.duration, eng.dt)
            for j, i in enumerate(chunk):
                out[i] = (eng.lane_results(j), eng.lane_events(j),
                          eng.lane_trace(j))
    return out


def run_batched(lane_specs: Sequence[Tuple[CampaignSpec, int]],
                max_lanes: int = _MAX_LANES_PER_ENGINE) -> List[dict]:
    """Like :func:`run_batched_detailed`, results only."""
    return [res for res, _events, _trace in
            run_batched_detailed(lane_specs, max_lanes)]


# -- sweep result table ---------------------------------------------------

_BAND_METRICS = ("cost", "accel_days", "eflop_hours_fp32", "preemptions",
                 "jobs_finished")


def _flatten_row(row: dict) -> dict:
    """Dotted-key flattening for CSV export; events_fired is serialized
    as one compact deterministic cell."""
    out: dict = {}

    def walk(prefix, v):
        if isinstance(v, dict):
            for k in sorted(v):
                walk(f"{prefix}.{k}" if prefix else str(k), v[k])
        else:
            out[prefix] = v

    for k, v in row.items():
        if k == "events_fired":
            out[k] = "|".join(
                ";".join(f"{kk}={ev[kk]}" for kk in sorted(ev))
                for ev in v)
        else:
            walk(k, v)
    return out


@dataclass
class SweepResult:
    """Per-lane campaign totals plus per-scenario summary bands.

    Rows are legacy ``results()`` dicts extended with ``scenario`` /
    ``seed`` / ``events_fired`` (the executed-event provenance both the
    batched and sequential engines record identically).  Sweeps run
    with ``collect="trace"`` additionally carry one
    :class:`~repro.core.events.CampaignTrace` per lane in ``traces``
    (row-aligned; reachable by name via :meth:`trace_for`) — rows stay
    plain dicts so CSV export and back-compat consumers are unaffected."""
    rows: List[dict]
    traces: Optional[List[Optional[CampaignTrace]]] = None

    def trace_for(self, scenario: str, seed: int) -> CampaignTrace:
        """The (scenario, seed) lane's typed event trace."""
        if self.traces is None:
            raise ValueError(
                "this sweep ran with collect='summary'; re-run with "
                "collect='trace' to record per-lane event traces")
        for row, tr in zip(self.rows, self.traces):
            if row["scenario"] == scenario and row["seed"] == seed:
                return tr
        raise KeyError((scenario, seed))

    def to_csv(self, path: Optional[str] = None) -> str:
        """Deterministic CSV of the per-lane rows: rows sorted by
        (scenario, seed), columns sorted by dotted key — byte-identical
        across runs of the same sweep, so CI artifacts diff cleanly."""
        import csv
        import io
        flat = sorted((_flatten_row(r) for r in self.rows),
                      key=lambda r: (str(r.get("scenario", "")),
                                     r.get("seed", 0)))
        cols = ["scenario", "seed"] + sorted(
            {k for r in flat for k in r} - {"scenario", "seed"})
        buf = io.StringIO()
        w = csv.DictWriter(buf, fieldnames=cols, restval="",
                           lineterminator="\n")
        w.writeheader()
        w.writerows(flat)
        text = buf.getvalue()
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text

    def scenario_names(self) -> List[str]:
        seen: List[str] = []
        for r in self.rows:
            if r["scenario"] not in seen:
                seen.append(r["scenario"])
        return seen

    def summary(self, metrics: Sequence[str] = _BAND_METRICS
                ) -> Dict[str, dict]:
        """Per-scenario {metric: {mean, p5, p95}} across seeds."""
        out: Dict[str, dict] = {}
        for name in self.scenario_names():
            vals = {m: np.array([r[m] for r in self.rows
                                 if r["scenario"] == name])
                    for m in metrics}
            out[name] = {
                "seeds": int(len(next(iter(vals.values())))),
                **{m: {"mean": float(np.mean(v)),
                       "p5": float(np.percentile(v, 5)),
                       "p95": float(np.percentile(v, 95))}
                   for m, v in vals.items()}}
        return out

    def table(self, metrics: Sequence[str] = ("cost", "accel_days",
                                              "preemptions")) -> str:
        """Plain-text planning table: one row per scenario, mean [p5, p95]
        bands per metric."""
        summ = self.summary(metrics)
        if not summ:
            return "(no sweep rows)"
        width = max(len(n) for n in summ) + 2
        cols = [f"{m} mean [p5, p95]" for m in metrics]
        lines = ["scenario".ljust(width) + "  ".join(c.rjust(30)
                                                     for c in cols)]
        for name, stats in summ.items():
            cells = []
            for m in metrics:
                s = stats[m]
                cells.append(f"{s['mean']:,.1f} "
                             f"[{s['p5']:,.1f}, {s['p95']:,.1f}]".rjust(30))
            lines.append(name.ljust(width) + "  ".join(cells))
        return "\n".join(lines)
