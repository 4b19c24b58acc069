"""Batched sweep engine correctness: every lane of a batched multi-
campaign sweep must report the same ``results()`` totals as a solo
``spec.run_solo()`` at the same (seed, spec) — including the paper
replay at seed 2021 — and money must conserve per lane.  Plus the
per-engine instance-ID determinism regression (IDs used to come from a
module-global ``itertools.count``, so they depended on how many
simulators ran earlier in the process)."""
import numpy as np
import pytest

from repro.core import sweep
from repro.core.api import run, sweep as api_sweep
from repro.core.provider import t4_catalog
from repro.core.provisioner import MultiCloudProvisioner
from repro.core.scenarios import (budget_floor_variants, default_suite,
                                  outage_grid, spot_ondemand_mixes)
from repro.core.simulator import CloudSimulator, SimConfig
from repro.core.spec import (CampaignSpec, CEOutage, PAPER_RAMP_EVENTS,
                             build_catalog, paper_spec, run_solo)
from tests.engine_equivalence import assert_results_match

# migrated call sites keep the historical underscore name
_assert_results_match = assert_results_match


def test_sweep_lanes_match_solo_campaigns():
    """The flagship sweep invariant, pinned on three lanes including the
    full paper replay at seed 2021: batched lane totals == solo run."""
    lanes = [(CampaignSpec(), 2021), (CampaignSpec(), 7),
             (outage_grid((60.0,), (12.0,))[0], 7)]
    sw = api_sweep([CampaignSpec(), outage_grid((60.0,), (12.0,))[0]],
                   [2021, 7], engine="batched")
    by_key = {(r["scenario"], r["seed"]): r for r in sw.rows}
    for sc, seed in lanes:
        solo, _ = run_solo(sc, seed)
        _assert_results_match(by_key[(sc.name, seed)], solo.to_dict())
    # and the seed-2021 paper lane reproduces the front door's replay
    replay = run(paper_spec(), seeds=2021)
    _assert_results_match(by_key[("paper", 2021)], replay.to_dict())
    # ... which are the paper's numbers
    paper_lane = by_key[("paper", 2021)]
    assert 14500 <= paper_lane["accel_days"] <= 17500
    assert 52000 <= paper_lane["cost"] <= 60000


def test_instance_ids_deterministic_per_engine():
    """Regression: IDs came from module-global itertools.count, so a
    sim's instance numbering depended on process history.  Every engine
    — array, object, and each batched lane — must number from 0."""
    for _ in range(2):          # second run must look identical
        sim = CloudSimulator(t4_catalog(), 1e6, SimConfig(duration_h=1.0))
        sim.prov.scale_to(50, 0.0)
        ids = sorted(i.id for i in sim.prov.live_instances())
        assert ids == list(range(50))
    for _ in range(2):
        prov = MultiCloudProvisioner(t4_catalog())
        prov.scale_to(50, 0.0)
        assert sorted(i.id for i in prov.live_instances()) \
            == list(range(50))
    # batched: every lane numbers its own instances from 0
    lanes = [sweep._prepare(CampaignSpec(duration_h=2.0), s)[1]
             for s in (1, 2)]
    eng = sweep.BatchedFleetEngine(lanes).run()
    for b in range(eng.B):
        lane_rows = (eng.i_lg[:eng.n] // eng.G) == b
        ids = np.sort(eng.i_id[:eng.n][lane_rows])
        assert ids[0] == 0
        assert len(np.unique(ids)) == len(ids)


def test_batched_money_conservation():
    """Per lane: charged $ == billed instance-hours x group rate
    (+ infra overhead), including compacted-away instances."""
    sc = CampaignSpec(duration_h=72.0, timeline=PAPER_RAMP_EVENTS,
                      budget=1e9)
    lanes = [sweep._prepare(sc, s)[1] for s in (5, 6)]
    eng = sweep.BatchedFleetEngine(lanes).run()
    hours = eng.billed_hours_by_lg()
    dollars = hours * eng.rate_h_lg
    for b in range(eng.B):
        lane_fleet = float(dollars.reshape(eng.B, eng.G)[b].sum())
        infra = float(eng.by_provider[b, eng.infra_col])
        assert lane_fleet + infra == pytest.approx(
            float(eng.spent[b]), rel=1e-9)
        assert infra > 0            # overhead charged per tick


def test_sequential_engine_matches_batched():
    """api.sweep(engine='sequential') is the reference loop; the
    batched engine must agree row by row."""
    scs = [CampaignSpec(duration_h=36.0),
           CampaignSpec(name="early-outage", duration_h=36.0,
                        timeline=PAPER_RAMP_EVENTS
                        + (CEOutage(12.0, 4.0, 1000),))]
    seeds = [1, 9]
    batched = api_sweep(scs, seeds, engine="batched")
    seq = api_sweep(scs, seeds, engine="sequential")
    assert [r["scenario"] for r in batched.rows] \
        == [r["scenario"] for r in seq.rows]
    for rb, rs in zip(batched.rows, seq.rows):
        _assert_results_match(rb, rs)


def test_sweep_summary_bands():
    sw = api_sweep([CampaignSpec(duration_h=48.0)], [1, 2, 3],
                   engine="batched")
    assert len(sw.rows) == 3
    summ = sw.summary()
    assert set(summ) == {"paper"}
    stats = summ["paper"]
    assert stats["seeds"] == 3
    for metric in ("cost", "accel_days", "preemptions"):
        s = stats[metric]
        assert s["p5"] <= s["mean"] <= s["p95"]
    table = sw.table()
    assert "paper" in table and "cost" in table


def test_scenario_library():
    suite = default_suite()
    names = [s.name for s in suite]
    assert len(names) == len(set(names)) and len(suite) >= 8
    assert sum(1 for s in suite if not s.spot) == 1
    # the on-demand split carves preemption-free capacity at o-d prices
    cat = build_catalog(spot_ondemand_mixes((0.5,))[0])
    assert "azure-od" in cat
    od = cat["azure-od"]
    assert od.spot_price_per_day == cat["azure"].ondemand_price_per_day
    assert all(r.preempt_rate_per_hour == 0.0 for r in od.regions)
    # price perturbation scales both price axes
    pp = build_catalog(CampaignSpec(price_scale=2.0))
    base = t4_catalog()
    assert pp["azure"].spot_price_per_day \
        == pytest.approx(2.0 * base["azure"].spot_price_per_day)
    grid = outage_grid((60.0, 252.0), (2.0, 12.0))
    assert len(grid) == 4
    assert {s.budget_floor_fraction
            for s in budget_floor_variants((0.1, 0.3))} == {0.1, 0.3}


def test_ondemand_costs_more_per_gpu_day():
    """Same ramp, same seed: the on-demand lane pays a much higher
    $/GPU-day and sees zero spot preemptions."""
    sw = api_sweep([CampaignSpec(duration_h=48.0, budget=1e9,
                                 timeline=PAPER_RAMP_EVENTS),
                    CampaignSpec(name="od", spot=False, duration_h=48.0,
                                 budget=1e9, timeline=PAPER_RAMP_EVENTS)],
                   [4], engine="batched")
    spot_row, od_row = sw.rows
    assert od_row["cost_per_accel_day"] \
        > 2.0 * spot_row["cost_per_accel_day"]
    assert od_row["cost"] > spot_row["cost"]
