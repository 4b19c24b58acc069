"""The compiled sweep engine (``engine="jax"``, core/sweep_jax.py).

Four contracts:

  * **statistical equivalence** (the acceptance bar): over the full
    ``scenarios.default_suite`` at 8 seeds, per-scenario mean and
    [p5, p95] bands on cost, GPU-days and jobs must sit inside the
    batched numpy engine's bands
    (``engine_equivalence.assert_statistically_equivalent``),
  * **event provenance is not statistical**: ``events_fired`` is
    reconstructed through the same timeline registry and must match the
    bit-identical engines record-for-record,
  * **one front door**: ``api.run/sweep(engine="jax")`` dispatch,
    the solo forced path, the no-trace-surface error, and the
    centralized allowed-engine sets the CLI shares,
  * **planning-grid scale**: every ``scenarios.planning_grid`` member
    shares one structural batch key, so the whole grid compiles into a
    single scan.
"""
import pytest

pytest.importorskip("jax")

from engine_equivalence import assert_statistically_equivalent
from repro.core import scenarios
from repro.core.api import (ENGINES, SOLO_ENGINES, SWEEP_ENGINES, run,
                            sweep)
from repro.core.spec import CampaignResult, paper_spec
from repro.core.sweep_jax import _prepare, run_jax


def _short(name="paper", **kw):
    from dataclasses import replace
    sc = next(s for s in scenarios.default_suite() if s.name == name)
    return replace(sc, **kw) if kw else sc


# -- the acceptance bar ----------------------------------------------------

@pytest.mark.slow
def test_jax_statistically_equivalent_full_suite():
    """ISSUE 7 acceptance: full default_suite, 8 seeds, mean/p5/p95
    bands on cost, GPU-days and jobs vs the batched numpy engine."""
    assert_statistically_equivalent(scenarios.default_suite(),
                                    list(range(8)))


def test_jax_statistically_equivalent_smoke():
    """The same contract at pytest-friendly cost: three suite members
    covering the budget-floor cap, a CE outage and a workload curve at
    reduced duration."""
    specs = [_short("paper", duration_h=96.0),
             _short("floor30", duration_h=96.0, budget=16000.0),
             _short("load-diurnal", duration_h=96.0)]
    assert_statistically_equivalent(specs, list(range(6)))


# -- event provenance ------------------------------------------------------

def test_jax_events_fired_match_batched():
    """events_fired is reconstructed through the registry's own apply
    bodies — schema- and value-identical to the bit-exact engines (the
    paper timeline: staged ramp + CE outage + budget-floor arming)."""
    sc = paper_spec()
    got = sweep([sc], [0], engine="jax")
    ref = sweep([sc], [0], engine="batched")
    assert got.rows[0]["events_fired"] == ref.rows[0]["events_fired"]


def test_jax_budget_floor_cap_event_recorded():
    """The in-scan budget-floor cap surfaces as the same budget_floor
    provenance record the other engines emit (its tick is data-driven,
    so only the schema and bounded timing are pinned)."""
    sc = _short("floor30", duration_h=168.0, budget=20000.0)
    res = run(sc, seeds=3, engine="jax")
    kinds = [e["event"] for e in res.events_fired]
    assert "budget_floor" in kinds
    cap = next(e for e in res.events_fired
               if e["event"] == "budget_floor")
    assert 0.0 <= cap["t"] <= sc.duration_h
    assert cap["target"] == sc.downscale_target


# -- the front door --------------------------------------------------------

def test_engine_sets_are_single_source():
    assert "jax" in SWEEP_ENGINES and "jax" in ENGINES
    assert "jax" not in SOLO_ENGINES
    assert "auto" in ENGINES and "auto" not in SWEEP_ENGINES


def test_unknown_engine_errors_share_one_message():
    sc = _short(duration_h=24.0)
    with pytest.raises(ValueError, match="unknown run engine 'nope'"):
        run(sc, seeds=1, engine="nope")
    with pytest.raises(ValueError, match="unknown sweep engine 'nope'"):
        sweep([sc], [1, 2], engine="nope")
    # "auto" dispatches in run() but is not a sweep engine
    with pytest.raises(ValueError, match="unknown sweep engine 'auto'"):
        sweep([sc], [1, 2], engine="auto")


def test_cli_engine_choices_track_api():
    """The campaigns CLI --engine choices derive from api.ENGINES (the
    drift this satellite closes)."""
    from repro.campaigns import main as cli_main
    try:
        cli_main(["run", "/nonexistent.spec.json", "--engine", "jax"])
    except FileNotFoundError:
        pass  # engine choice accepted; the spec path (deliberately) not
    with pytest.raises(SystemExit):
        cli_main(["run", "/nonexistent.spec.json", "--engine", "nope"])


def test_jax_solo_forced_run_returns_campaign_result():
    sc = _short(duration_h=48.0)
    res = run(sc, seeds=11, engine="jax")
    assert isinstance(res, CampaignResult)
    assert res.engine == "jax" and res.seed == 11
    assert res.cost > 0 and res.accel_days > 0


def test_jax_has_no_trace_surface():
    sc = _short(duration_h=24.0)
    with pytest.raises(ValueError, match="statistical"):
        run(sc, seeds=1, engine="jax", collect="trace")
    with pytest.raises(ValueError, match="statistical"):
        sweep([sc], [1, 2], engine="jax", collect="trace")


# -- planning-grid scale ---------------------------------------------------

def test_planning_grid_shares_one_batch_key():
    grid = scenarios.planning_grid()
    assert len(grid) == 60
    assert len({s.name for s in grid}) == 60
    keys = {_prepare(s, 0)[0] for s in grid}
    assert len(keys) == 1, "grid members must compile into one scan"


def test_jax_grid_slice_runs_in_one_engine_batch():
    from dataclasses import replace
    grid = [replace(s, duration_h=24.0)
            for s in scenarios.planning_grid((0.9, 1.1), (0.2,),
                                             (58000.0,))]
    sw = sweep(grid, [0, 1], engine="jax")
    assert len(sw.rows) == len(grid) * 2
    costs = {r["scenario"]: r["cost"] for r in sw.rows}
    assert costs["grid-p090-f20-b58k"] < costs["grid-p110-f20-b58k"]


# -- engine internals ------------------------------------------------------

def test_jax_batches_by_structural_key():
    """Lanes with different catalogs land in different compiled batches;
    lanes differing only in price/budget share one."""
    a = _short(duration_h=24.0)
    b = _short("hetero", duration_h=24.0)
    out = run_jax([(a, 0), (b, 0), (a, 1)])
    assert len(out) == 3
    assert out[0]["cost"] != out[1]["cost"]


def test_jax_engine_is_deterministic():
    lanes = [(_short(duration_h=48.0), s) for s in (0, 1)]
    r1 = run_jax(lanes)
    r2 = run_jax(lanes)
    assert r1 == r2


def test_jax_results_schema_matches_batched():
    sc = _short(duration_h=48.0)
    gj = sweep([sc], [5], engine="jax").rows[0]
    gb = sweep([sc], [5], engine="batched").rows[0]
    assert set(gj) == set(gb)
    assert set(gj["budget"]) == set(gb["budget"])
    assert set(gj["by_provider"]) == set(gb["by_provider"])


def test_jax_pallas_interpret_path_matches_ref_path():
    """use_pallas=True on CPU runs every tick op through the Pallas
    kernels in interpret mode; integer semantics must match the jnp
    oracle path exactly (same seeds, same scan)."""
    lanes = [(_short(duration_h=24.0), s) for s in (0, 1)]
    ref = run_jax(lanes, use_pallas=False)
    pal = run_jax(lanes, use_pallas=True)
    assert ref == pal


# -- the bake runs once per distinct spec ------------------------------------

def _bake_per_lane(lanes):
    """The bake as it was done lane by lane before it was shared per
    spec: the oracle for ``JaxSweepEngine.__init__``'s planes and
    constants.  Returns ``(planes, consts, seg_of_tick, is_seg_start)``."""
    import numpy as np

    from repro.core import timeline as timeline_registry
    from repro.core.sweep_jax import JaxLaneOps

    B, ref = len(lanes), lanes[0]
    pairs = ref.pairs
    G = len(pairs)
    dt, duration = float(ref.spec.dt_h), float(ref.spec.duration_h)
    g_provider = [p.name for p, _ in pairs]
    providers = list(dict.fromkeys(g_provider))
    prov_onehot = np.zeros((G, len(providers)), np.float32)
    prov_onehot[np.arange(G), [providers.index(n) for n in g_provider]] = 1.0
    g_nat = np.array([p.nat_idle_timeout_s for p, _ in pairs])
    times, now = [], 0.0
    while now < duration:
        times.append(now)
        now += dt
    tick_times = np.array(times)
    N = len(times)

    evs_b, fts_b, seg_set = [], [], {0}
    for ln in lanes:
        evs = timeline_registry.compile_timeline(ln.spec.timeline)
        ft = np.searchsorted(tick_times, np.array([e[0] for e in evs]),
                             "left") if evs else np.zeros(0, np.int64)
        evs_b.append(evs)
        fts_b.append(ft)
        seg_set.update(int(t) for t in ft if t < N)
    seg_ticks = np.array(sorted(seg_set), np.int64)
    n_seg = len(seg_ticks)
    seg_of_tick = (np.searchsorted(seg_ticks, np.arange(N), "right")
                   - 1).astype(np.int32)
    is_seg_start = np.zeros(N, bool)
    is_seg_start[seg_ticks] = True

    rate = np.zeros((n_seg, B, G), np.float32)
    cap = np.zeros((n_seg, B, G), np.int32)
    outage = np.zeros((n_seg, B), bool)
    floor = np.zeros((n_seg, B), np.float32)
    downscale = np.zeros((n_seg, B), np.int32)
    minq = np.zeros((n_seg, B), np.int32)
    n_unc = np.full((n_seg, B), -1, np.int32)
    n_cap = np.full((n_seg, B), -1, np.int32)
    origin_up = np.ones((n_seg, B, G), bool)
    dp_degrade = np.ones((n_seg, B, G))
    dp_flush = np.zeros((n_seg, B, G), bool)
    for b, ln in enumerate(lanes):
        ops_u = JaxLaneOps(ln.spec, ln.pairs, budget_capped=False)
        ops_c = JaxLaneOps(ln.spec, ln.pairs, budget_capped=True)
        by_tick = {}
        for (_t, kind, arg), ft in zip(evs_b[b], fts_b[b]):
            if ft < N:
                by_tick.setdefault(int(ft), []).append((kind, arg))
        for s, st in enumerate(seg_ticks):
            ops_u.scale_n = ops_c.scale_n = None
            ops_u.flush_edge[:] = False
            for kind, arg in by_tick.get(int(st), []):
                timeline_registry.apply_op(ops_u, kind, arg, 0.0)
                timeline_registry.apply_op(ops_c, kind, arg, 0.0)
            rate[s, b] = ops_u.rate_h()
            cap[s, b] = ops_u.cap
            outage[s, b] = ops_u.outage
            floor[s, b] = ops_u.floor_fraction
            downscale[s, b] = ops_u.downscale_target
            minq[s, b] = ops_u.min_queue_eff
            origin_up[s, b] = ops_u.origin_up
            dp_degrade[s, b] = ops_u.dp_degrade
            dp_flush[s, b] = ops_u.flush_edge
            if ops_u.scale_n is not None:
                n_unc[s, b] = ops_u.scale_n
            if ops_c.scale_n is not None:
                n_cap[s, b] = ops_c.scale_n
    planes = {"rate": rate, "cap": cap, "outage": outage, "floor": floor,
              "downscale": downscale, "minq": minq, "n_unc": n_unc,
              "n_cap": n_cap}

    lease = np.array([ln.spec.lease_interval_s for ln in lanes])
    nat_g = (~(lease[:, None] < g_nat[None, :])).astype(np.int32)
    wall = np.array([ln.spec.job_wall_h for ln in lanes])
    ckpt = np.array([ln.spec.job_checkpoint_h for ln in lanes])
    L = max(1, int(np.max(np.floor(wall / ckpt)) + 1))
    wfin1 = np.maximum(0, np.ceil(wall / dt - 1e-9).astype(np.int64) - 1)
    W = int(wfin1.max()) + 1
    finmask = (np.arange(W)[None, :] >= wfin1[:, None]).astype(np.int32)
    lvl_of_w = np.minimum(np.floor(np.arange(W)[None, :] * dt
                                   / ckpt[:, None] + 1e-9)
                          .astype(np.int64), L - 1)
    M_wl = np.zeros((B, W, L), np.float32)
    M_wl[np.arange(B)[:, None], np.arange(W)[None, :], lvl_of_w] = 1.0
    lev_of_j = np.concatenate([np.arange(L - 1, -1, -1), [0]])
    w0_of_j = np.minimum(np.rint(lev_of_j[None, :] * ckpt[:, None] / dt)
                         .astype(np.int64), W - 1)
    w0_of_j[:, L] = 0
    M_jw = np.zeros((B, L + 1, W), np.float32)
    M_jw[np.arange(B)[:, None], np.arange(L + 1)[None, :], w0_of_j] = 1.0

    dp = ref.spec.dataplane
    dp_size = float(ref.spec.job_input_gb)
    origins_g = [dp.origin_for(n) if dp is not None else None
                 for n in g_provider]
    dp_active = dp is not None and bool(dp.origins)
    dp_consts = {}
    if dp_active and dp_size > 0.0:
        def ticks(gbps):
            gbps = np.asarray(gbps, np.float64)
            hours = dp_size * 8.0 / np.where(gbps > 0.0, gbps, 1.0) / 3600.0
            t = np.maximum(1, np.ceil(hours / dt - 1e-9).astype(np.int64))
            return np.where(gbps > 0.0, t, 0)

        r_g = np.array([o.cache_hit_rate if o else 0.0 for o in origins_g],
                       np.float32)
        bw_g = np.array([o.bandwidth_gbps if o else 0.0 for o in origins_g])
        hbw_g = np.array([(o.cache_bandwidth_gbps
                           if o.cache_bandwidth_gbps > 0.0
                           else o.bandwidth_gbps) if o else 0.0
                          for o in origins_g])
        S_hit = ticks(hbw_g)
        S_miss = ticks(bw_g[None, None, :] * dp_degrade).astype(np.int32)
        S_max = int(max(S_hit.max(), S_miss.max()))
        W_ext = W + S_max
        finmask = (np.arange(W_ext)[None, :]
                   >= S_max + wfin1[:, None]).astype(np.int32)
        lvl_of_ext = np.minimum(np.floor(
            np.clip(np.arange(W_ext)[None, :] - S_max, 0, None)
            * dt / ckpt[:, None] + 1e-9).astype(np.int64), L - 1)
        M_wl = np.zeros((B, W_ext, L), np.float32)
        M_wl[np.arange(B)[:, None], np.arange(W_ext)[None, :],
             lvl_of_ext] = 1.0
        bi = np.arange(B)[:, None, None]
        gi = np.arange(G)[None, :, None]
        ji = np.arange(L + 1)[None, None, :]
        E_hit = np.zeros((B, G, L + 1, W_ext), np.float32)
        E_hit[bi, gi, ji, S_max + w0_of_j[:, None, :]
              - S_hit[None, :, None]] = 1.0
        E_miss = np.zeros((n_seg, B, G, L + 1, W_ext), np.float32)
        for s in range(n_seg):
            E_miss[s][bi, gi, ji, S_max + w0_of_j[:, None, :]
                      - S_miss[s][:, :, None]] = 1.0
        planes.update(S_miss=S_miss, E_miss=E_miss, dp_flush=dp_flush)
        n_ = np.arange(1, 201)[:, None]
        dp_consts = {
            "dp_r_g": r_g,
            "dp_has_g": np.array([o is not None for o in origins_g],
                                 np.float32),
            "dp_usd_miss_g": np.array(
                [dp_size * o.egress_usd_per_gb if o else 0.0
                 for o in origins_g], np.float32),
            "dp_loss_g": np.where(
                r_g > 0.0,
                np.modf(n_ * r_g[None, :].astype(np.float64))[0].mean(0),
                0.0).astype(np.float32),
            "S_hit_g": S_hit.astype(np.float32),
            "E_hit": E_hit}
    if dp_active:
        planes["origin_up"] = origin_up
    consts = {
        "prov_onehot": prov_onehot,
        "pre_rate_g": np.array([r.preempt_rate_per_hour for _, r in pairs],
                               np.float32),
        "pre_scale_g": np.array([r.preempt_scale_at_full for _, r in pairs],
                                np.float32),
        "nat_g": nat_g,
        "finmask_rg": np.repeat(finmask, G, axis=0),
        "M_wl": M_wl,
        "M_jw": M_jw,
        "overhead": np.array([ln.spec.overhead_per_day for ln in lanes],
                             np.float32),
        "budget": np.array([ln.spec.budget for ln in lanes], np.float32),
        "dt": np.float32(dt),
        "seeds": np.array([ln.seed for ln in lanes], np.uint32),
        **dp_consts,
    }
    return planes, consts, seg_of_tick, is_seg_start


def _json_copy(spec):
    """An equal spec that is another object (a JSON round trip)."""
    import json

    from repro.core.spec import CampaignSpec
    copy = CampaignSpec.from_dict(json.loads(spec.to_json()))
    assert copy == spec and copy is not spec
    return copy


def _paper_mix():
    """Paper-catalog specs, 72 h: planning-grid variants, an outage-grid
    member, workload and price curves, and shifts of price, capacity and
    the budget floor — one batch key, timelines that differ."""
    from dataclasses import replace

    from repro.core.timeline import BudgetFloor, CapacityShift, PriceShift
    short = {"duration_h": 72.0}
    grid = [replace(s, **short) for s in scenarios.planning_grid(
        (0.9, 1.1), (0.2, 0.3), (58000.0,))]
    outage = replace(scenarios.outage_grid((30.0,), (2.0,))[0], **short)
    shifts = replace(grid[0], name="shifts", timeline=(
        scenarios.PAPER_RAMP_EVENTS[:3] + (
            BudgetFloor(20.0, 0.3, 500), PriceShift(30.0, 1.2),
            CapacityShift(40.0, 0.5), PriceShift(50.0, 0.9))))
    return grid + [outage, shifts, _short("load-diurnal", **short),
                   _short("curve-drift-up", **short)]


def _dataplane_mix():
    """Data-plane specs, 72 h, with an origin outage, a degrade and a
    cache flush inside the window: one batch key."""
    from dataclasses import replace

    from repro.core.timeline import CacheFlush, OriginDegrade, OriginOutage
    base = replace(scenarios.dataplane_burst(), duration_h=72.0,
                   timeline=scenarios.PAPER_RAMP_EVENTS[:3] + (
                       OriginOutage(10.0, 6.0, "azure"),
                       OriginDegrade(20.0, 0.5, "aws"),
                       CacheFlush(30.0, "azure"),
                       OriginDegrade(40.0, 0.5, "aws")))
    late = replace(base, name="late", budget=40000.0,
                   timeline=scenarios.PAPER_RAMP_EVENTS[:3] + (
                       CacheFlush(25.0, "azure"),
                       OriginOutage(35.0, 4.0, "gcp")))
    return [base, late]


def _mixed_lanes(specs):
    """Every spec under repeated seeds, once more as the same object and
    once as an equal copy, interleaved."""
    lanes = []
    for i, sc in enumerate(specs):
        lanes += [(sc, 7), (_json_copy(sc), 3 + i % 2), (sc, 3)]
    return lanes[0::2] + lanes[1::2]


@pytest.mark.parametrize("mix", ["paper", "dataplane", "distinct"])
def test_bake_per_spec_equals_the_per_lane_bake(mix):
    """Baking once per distinct spec and gathering to the lanes gives
    every plane and constant of the per-lane bake, bit for bit: equal
    specs as one object or as copies, seeds repeated across specs, and
    (``distinct``) a batch in which no two lanes share a spec."""
    import numpy as np

    from repro.core.sweep_jax import JaxSweepEngine
    from repro.core.timeline import compile_timeline
    if mix == "distinct":
        lane_specs = [(s, i) for i, s in enumerate(_paper_mix())]
    else:
        specs = _paper_mix() if mix == "paper" else _dataplane_mix()
        lane_specs = _mixed_lanes(specs)
    prepared = [_prepare(sc, seed) for sc, seed in lane_specs]
    assert len({key for key, _ln in prepared}) == 1
    lanes = [ln for _key, ln in prepared]
    eng = JaxSweepEngine(lanes, use_pallas=False)
    planes, consts, seg_of_tick, is_seg_start = _bake_per_lane(lanes)
    if mix == "dataplane":
        assert {"E_miss", "origin_up", "dp_flush"} <= set(eng.planes)
        assert eng.planes["dp_flush"].any()
        assert not eng.planes["origin_up"].all()
    assert set(eng.planes) == set(planes)
    assert set(eng.consts) == set(consts)
    for got, want in ((eng.planes, planes), (eng.consts, consts)):
        for k, v in want.items():
            assert got[k].dtype == v.dtype and got[k].shape == v.shape, k
            assert np.array_equal(got[k], v), k
    assert np.array_equal(eng.seg_of_tick, seg_of_tick)
    assert np.array_equal(eng.is_seg_start, is_seg_start)
    assert eng._evs == [compile_timeline(ln.spec.timeline) for ln in lanes]


def test_distinct_rows_by_value_and_identity():
    from repro.core.sweep_jax import _distinct
    a, b = _short("paper"), _short("floor30")
    unhashable = [1]
    rows, firsts = _distinct([a, b, _json_copy(a), a, unhashable, b,
                              unhashable, [1]])
    assert rows == [0, 1, 0, 0, 2, 1, 2, 3]
    assert firsts == [0, 1, 4, 7]


def test_run_jax_prepares_once_per_spec_and_keeps_input_order(monkeypatch):
    """Lanes of two batch keys, interleaved, with an equal copy of one
    spec: ``_prepare`` runs once per distinct spec, and the rows and
    ``events_fired`` come back in input order, equal to those of each
    spec's lanes run on their own; ``engine.bake`` counts the lanes and
    the distinct specs it baked."""
    from repro import obs
    from repro.core import sweep_jax
    a = _short(duration_h=24.0)
    h = _short("hetero", duration_h=24.0)
    a2 = _json_copy(a)
    lane_specs = [(a, 0), (h, 5), (a2, 1), (a, 2), (h, 0), (a2, 0)]
    calls = []
    prepare = sweep_jax._prepare

    def counted(sc, seed):
        calls.append(sc)
        return prepare(sc, seed)

    monkeypatch.setattr(sweep_jax, "_prepare", counted)
    got = sweep_jax.run_jax_detailed(lane_specs)
    assert calls == [a, h]
    bake = [(s.counters["bake_lanes"], s.counters["bake_specs"])
            for s in obs.calls()[-1].spans if s.name == "engine.bake"]
    assert bake == [(4, 1), (2, 1)]

    def alone(sc, seeds):
        eng = sweep_jax.JaxSweepEngine(
            [prepare(sc, seed)[1] for seed in seeds]).run()
        return [(eng.lane_results(j), eng.lane_events(j), None)
                for j in range(len(seeds))]

    alone_a = alone(a, [0, 1, 2, 0])
    alone_h = alone(h, [5, 0])
    want = [alone_a[0], alone_h[0], alone_a[1], alone_a[2], alone_h[1],
            alone_a[3]]
    assert len(got) == len(want)
    for (res, evs, tr), (res_w, evs_w, _tr) in zip(got, want):
        assert res == res_w and evs == evs_w and tr is None
    assert got[0] == got[5] and got[0][0] != got[2][0]


# -- readback: one replay per (spec, cap tick), results from columns ---------

def _events_per_lane(eng, b):
    """The provenance replay as it was done lane by lane before it was
    shared per (spec, cap tick): the oracle for ``lane_events``."""
    from repro.core import timeline as timeline_registry
    from repro.core.sweep_jax import JaxLaneOps
    ln = eng.lanes[b]
    ops = JaxLaneOps(ln.spec, ln.pairs)
    cap_tick = int(eng.out["cap_tick"][b]) if eng.out is not None else -1
    by_tick = {}
    for (t, kind, arg), ft in zip(eng._evs[b], eng._fts[b]):
        if ft < eng.N:
            by_tick.setdefault(int(ft), []).append((kind, arg))
    ticks = sorted(set(by_tick) | ({cap_tick} if cap_tick >= 0 else set()))
    recs = []
    for ft in ticks:
        now = float(eng.tick_times[ft])
        ops.budget_capped = 0 <= cap_tick <= ft
        if ft == cap_tick:
            recs.append(timeline_registry.apply_budget_cap(ops, now))
        for kind, arg in by_tick.get(ft, []):
            recs.append(timeline_registry.apply_op(ops, kind, arg, now))
    return recs


def _results_per_lane(eng, b):
    """``lane_results`` as it was read element by element before it
    read columns: the oracle for the column-wise body."""
    out = eng.out
    sc = eng.lanes[b].spec
    busy_by_prov = {}
    for pidx, name in enumerate(eng.providers):
        h = float(out["busy_prov"][b, pidx])
        if h > 0:
            busy_by_prov[name] = h
    if eng.homogeneous:
        eflop = float(out["busy_h"][b]) * sc.accel_tflops * 1e12 / 1e18
    else:
        eflop = sum(
            h * (eng.provider_tflops.get(name) or sc.accel_tflops)
            for name, h in busy_by_prov.items()) * 1e12 / 1e18
    spent = float(out["spent"][b])
    budget = float(eng.consts["budget"][b])
    raw_by_prov = {}
    for pidx, name in enumerate(eng.providers):
        v = float(out["by_prov"][b, pidx])
        if v > 0:
            raw_by_prov[name] = v
    for g, base in enumerate(eng.dp_base_g):
        e = float(out["egress_g"][b, g])
        if e > 0:
            raw_by_prov[base] = raw_by_prov.get(base, 0.0) + e
    ledger_by_prov = {k: round(v, 2) for k, v in raw_by_prov.items()}
    infra = float(out["infra"][b])
    if infra > 0:
        ledger_by_prov["infra"] = round(infra, 2)
    by_provider = {}
    for g, name in enumerate(eng.g_provider):
        by_provider[name] = by_provider.get(name, 0) \
            + int(out["live_g"][b, g])
    accel = float(out["accel"][b])
    hits, misses = float(out["hits"][b]), float(out["misses"][b])
    return {
        "accel_hours": round(accel, 1),
        "accel_days": round(accel / 24.0, 1),
        "busy_hours": round(float(out["busy_h"][b]), 1),
        "busy_hours_by_provider": {
            k: round(v, 1) for k, v in sorted(busy_by_prov.items())},
        "eflop_hours_fp32": round(eflop, 3),
        "cost": round(spent, 2),
        "cost_per_accel_day": round(spent / max(accel / 24.0, 1e-9), 2),
        "preemptions": int(out["pre_ct"][b]),
        "nat_drops": int(out["nat_ct"][b]),
        "jobs_finished": int(out["fin_ct"][b]),
        "egress_usd": round(float(out["egress_g"][b].sum()), 2),
        "stagein_hours": round(float(out["stage_t"][b]) * eng.dt, 1),
        "cache_hit_fraction": round(hits / (hits + misses), 4)
        if hits + misses else 0.0,
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


def _capped_engine(lane_specs):
    """A run engine over ``lane_specs`` and a second engine over the same
    lanes holding the first one's outputs with the cap ticks replaced:
    lane by lane, never (-1), at tick 0, at each spec's event ticks and
    past its last event.  A spec's lanes take three cap ticks in turn,
    one of them -1, so they differ and recur, and equal cap ticks recur
    under different specs."""
    import numpy as np

    from repro.core.sweep_jax import JaxSweepEngine, _distinct
    lanes = [_prepare(sc, seed)[1] for sc, seed in lane_specs]
    ran = JaxSweepEngine(lanes, use_pallas=False).run()
    eng = JaxSweepEngine(lanes, use_pallas=False)
    rows, _firsts = _distinct([ln.spec for ln in lanes])
    seen = {}
    caps = []
    for b, u in enumerate(rows):
        k = seen[u] = seen.get(u, -1) + 1
        fts = sorted({int(t) for t in eng._fts[b] if t < eng.N})
        ticks = [0, *fts[1::2], min(fts[-1] + 3, eng.N - 1)]
        caps.append(-1 if k % 3 == 0 else ticks[(u + k % 3) % len(ticks)])
    eng.out = dict(ran.out, cap_tick=np.array(caps, np.int32))
    return ran, eng


def _replay_keys(eng):
    from repro.core.sweep_jax import _distinct
    rows, _firsts = _distinct([ln.spec for ln in eng.lanes])
    return {(u, int(t)) for u, t in zip(rows, eng.out["cap_tick"])}


@pytest.mark.parametrize("mix", ["paper", "dataplane"])
def test_memoised_events_equal_the_per_lane_replay(mix):
    """``lane_events`` gives every lane the records of its own replay,
    in input order: the scan's cap ticks, then cap ticks that differ
    between lanes of one spec (never, tick 0, at and between event
    ticks, after the last), equal specs as one object and as JSON
    copies, seeds shared across specs; ``engine.events`` counts the
    lanes and the distinct (spec, cap tick) keys it replayed."""
    from repro import obs
    specs = _paper_mix() if mix == "paper" else _dataplane_mix()
    ran, eng = _capped_engine(_mixed_lanes(specs) * 2)
    for e in (ran, eng):
        with obs.call("test.readback"), obs.span("engine.events"):
            got = [e.lane_events(b) for b in range(e.B)]
        assert got == [_events_per_lane(e, b) for b in range(e.B)]
        counters = obs.calls()[-1].spans[-1].counters
        assert counters["replay_lanes"] == e.B
        assert counters["replay_keys"] == len(_replay_keys(e))
    # the keys engage: lanes of one spec differ in cap tick, and the
    # cache still replays far fewer keys than lanes
    by_spec = {}
    for ln, t in zip(eng.lanes, eng.out["cap_tick"]):
        by_spec.setdefault(ln.spec, set()).add(int(t))
    assert all(len(ts) > 1 and -1 in ts for ts in by_spec.values())
    assert len(_replay_keys(eng)) < eng.B
    kinds = {r["event"] for recs in got for r in recs}
    assert "budget_floor" in kinds
    if mix == "dataplane":
        assert {"origin_outage_on", "origin_degrade", "cache_flush"} <= kinds


def test_memoised_events_are_copies_no_lane_shares():
    """Changing one lane's records, or a nested value in them, leaves
    another lane's records and the cache as they were."""
    from repro.core.sweep_jax import JaxSweepEngine
    sc = _short(duration_h=72.0)
    eng = JaxSweepEngine([_prepare(sc, s)[1] for s in (0, 1, 2)],
                         use_pallas=False).run()
    want = _events_per_lane(eng, 1)
    assert want and eng.out["cap_tick"][0] == eng.out["cap_tick"][1]
    first = eng.lane_events(0)
    assert first == want
    first[0]["t"] = -1.0
    first.append({"event": "planted"})
    second = eng.lane_events(1)
    assert second == want and eng.lane_events(0) == want
    assert all(a is not b for a, b in zip(first, second))

    nested = [{"t": 0.0, "event": "nested", "targets": [1, 2]}]
    eng._replays.clear()
    eng._replay = lambda b, cap_tick: [dict(r, targets=list(r["targets"]))
                                       for r in nested]
    got = eng.lane_events(0)
    got[0]["targets"].append(3)
    assert eng.lane_events(2) == nested and eng.lane_events(0) == nested


def test_run_jax_counts_replays_on_engine_events():
    """A sweep call's ``engine.events`` span carries ``replay_lanes``
    (every lane) and ``replay_keys`` (one a distinct spec and cap tick)
    beside ``engine.bake``'s ``bake_lanes`` / ``bake_specs``."""
    from repro import obs
    from repro.core import sweep_jax
    specs = _paper_mix()[:4]
    lane_specs = [(sc, seed) for sc in specs for seed in (0, 1, 2)]
    sweep_jax.run_jax_detailed(lane_specs)
    spans = {s.name: s.counters for s in obs.calls()[-1].spans}
    eng = sweep_jax.JaxSweepEngine(
        [_prepare(sc, seed)[1] for sc, seed in lane_specs]).run()
    assert spans["engine.bake"]["bake_specs"] == 4
    assert spans["engine.events"]["replay_lanes"] == 12
    assert spans["engine.events"]["replay_keys"] == len(_replay_keys(eng))
    assert 4 <= spans["engine.events"]["replay_keys"] < 12


@pytest.mark.parametrize("variant", ["plain", "dataplane", "hetero", "nat"])
def test_column_results_equal_the_per_lane_read(variant):
    """``lane_results`` read from columns gives each lane's dict of the
    element-by-element read, byte for byte as JSON, with its keys sorted
    and in their own order: the paper spec, the data plane (egress,
    cache hits and misses, stage-in time), providers with their own
    TFLOPS (``homogeneous`` False) and a lease over Azure's NAT
    timeout."""
    import json

    from repro.core import sweep_jax
    from repro.core.spec import CampaignSpec
    spec = {"plain": CampaignSpec(),
            "dataplane": scenarios.dataplane_burst(),
            "hetero": scenarios.heterogeneous_burst(),
            "nat": CampaignSpec(lease_interval_s=300.0)}[variant]
    eng = sweep_jax.JaxSweepEngine(
        [_prepare(spec, s)[1] for s in (0, 3, 2 ** 31 - 1)],
        use_pallas=False).run()
    got = [eng.lane_results(b) for b in range(eng.B)]
    want = [_results_per_lane(eng, b) for b in range(eng.B)]
    for g, w in zip(got, want):
        assert json.dumps(g, sort_keys=True) == json.dumps(w, sort_keys=True)
        assert json.dumps(g) == json.dumps(w), "key order"
    assert eng.homogeneous == (variant != "hetero")
    if variant == "dataplane":
        assert all(r["egress_usd"] > 0 and r["stagein_hours"] > 0
                   and 0 < r["cache_hit_fraction"] < 1 for r in got)
    if variant == "nat":
        assert any(r["nat_drops"] > 0 for r in got)


# -- staging: one packed buffer each way -----------------------------------

def _bits(a):
    """What a bit-for-bit comparison compares: dtype, shape and bytes."""
    import numpy as np
    a = np.asarray(a)
    return a.dtype.name, a.shape, a.tobytes()


def _word_arrays():
    """Arrays of every dtype a packed buffer carries: signed zero, the
    infinities and a NaN with a payload, the integers' extremes, bools,
    a 0-d scalar and a 5-D array."""
    import numpy as np
    rng = np.random.default_rng(5)
    nan = np.array([0x7FC00123], np.uint32).view(np.float32)[0]
    return {
        "planes": {
            "f": np.array([0.0, -0.0, np.inf, -np.inf, nan, 1e-45, -3.5],
                          np.float32),
            "m": rng.random((2, 3, 4)) < 0.5,
            "e5": rng.standard_normal((2, 1, 3, 2, 4)).astype(np.float32)},
        "consts": {
            "i": np.array([[-2 ** 31, -1, 0], [1, 7, 2 ** 31 - 1]],
                          np.int32),
            "dt": np.float32(0.25),
            "u": np.array([0, 1, 2 ** 32 - 1, 2 ** 31], np.uint32),
            "one": np.array([True])}}


def test_pack_round_trips_bit_for_bit():
    """Host packing, unpacking on the host and inside a jit, and
    packing inside a jit all keep every array's bits, dtype and
    shape; the layout is a hashable function of keys, shapes and
    dtypes alone, and starts each array on a row of 128 words, with
    no overlap and no gap of a row or more."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.sweep_jax import _layout, _pack, _unpack
    groups = _word_arrays()
    want = {g: {k: _bits(a) for k, a in arrs.items()}
            for g, arrs in groups.items()}
    layout = _layout(groups)
    hash(layout)
    assert layout == _layout(
        {g: {k: jax.ShapeDtypeStruct(np.shape(a), a.dtype)
             for k, a in arrs.items()} for g, arrs in groups.items()})
    offs = [off for _g, _k, off, _s, _d in layout]
    ends = [off + int(np.prod(shape)) for _g, _k, off, shape, _d in layout]
    assert offs[0] == 0 and all(off % 128 == 0 for off in offs)
    assert all(0 <= nxt - end < 128 for end, nxt in zip(ends, offs[1:]))
    buf = _pack(groups, layout)
    assert buf.dtype == np.uint32 and buf.shape == (ends[-1],)

    def bits(got):
        return {g: {k: _bits(a) for k, a in arrs.items()}
                for g, arrs in got.items()}

    assert bits(_unpack(buf, layout)) == want
    on_device = jax.jit(_unpack, static_argnums=1)(jnp.asarray(buf), layout)
    assert bits(on_device) == want
    packed = jax.jit(_pack, static_argnums=1)(
        jax.tree.map(jnp.asarray, groups), layout)
    assert _bits(packed) == _bits(buf)


def test_layout_refuses_an_array_that_is_not_one_word():
    import numpy as np

    from repro.core.sweep_jax import _layout
    with pytest.raises(TypeError, match="float64"):
        _layout({"consts": {"dt": np.float64(0.25)}})


@pytest.mark.parametrize("variant", ["plain", "dataplane", "nat"])
def test_packed_run_equals_the_unpacked_scan(variant):
    """``run``'s packed entry gives every output of a jit of the scan
    itself, fed the engine's dicts, bit for bit: the plain scan, the
    data plane (``dp_gating`` and ``dp_staging``) and a lease over
    Azure's 240 s NAT timeout (``nat_any``)."""
    import jax
    import numpy as np

    from repro.core import sweep_jax
    from repro.core.spec import CampaignSpec
    spec = {"plain": CampaignSpec(),
            "dataplane": scenarios.dataplane_burst(),
            "nat": CampaignSpec(lease_interval_s=300.0)}[variant]
    eng = sweep_jax.JaxSweepEngine(
        [_prepare(spec, s)[1] for s in (0, 3, 2 ** 31 - 1)],
        use_pallas=False)
    flags = dict(nat_any=eng.nat_any, use_pallas=False,
                 dp_gating=eng.dp_active, dp_staging=eng.dp_staging)
    assert (flags["nat_any"], flags["dp_gating"], flags["dp_staging"]) \
        == {"plain": (False, False, False), "dataplane": (False, True, True),
            "nat": (True, False, False)}[variant]
    eng.run()
    scan = jax.jit(sweep_jax._scan, static_argnames=list(flags))
    want = scan(eng.planes, eng.consts,
                (np.arange(eng.N, dtype=np.int32), eng.seg_of_tick,
                 eng.is_seg_start), **flags)
    assert set(eng.out) == set(want)
    for k, v in want.items():
        assert _bits(eng.out[k]) == _bits(v), k


def test_run_stages_and_fetches_one_buffer_each_way():
    """A sweep call puts one buffer on the device and copies one back,
    and a warm call of the same shape compiles nothing."""
    from repro import obs
    from repro.core import sweep_jax
    lanes = [(_short(duration_h=24.0), s) for s in (0, 1)]
    for _call in range(2):
        sweep_jax.run_jax_detailed(lanes)
        call = obs.calls()[-1]
        spans = {s.name: s.counters for s in call.spans}
        assert spans["engine.put"]["h2d_arrays"] == 1
        assert spans["engine.fetch"]["d2h_arrays"] == 1
        assert spans["engine.put"]["h2d_bytes"] > 0
        assert spans["engine.fetch"]["d2h_bytes"] > 0
    assert call.counters.get("compiles", 0) == 0
