"""Array-engine correctness: the vectorized struct-of-arrays engine
(core/fleet.py) must be indistinguishable from the seed dataclass engine,
and its billing must conserve money (charged $ == instance-hours x rate).

These tests run without hypothesis; a hypothesis-powered randomized
schedule identity test rides along where hypothesis is installed.
"""
import numpy as np
import pytest

from repro.core.overlay import Job
from repro.core.provider import heterogeneous_catalog, t4_catalog
from repro.core.simulator import CloudSimulator, SimConfig
from repro.core.spec import (CampaignSpec, CEOutage, SetTarget, paper_spec,
                             run_solo)
from tests.engine_equivalence import assert_results_match


def _assert_results_match(a, o):
    """Shared comparison policy (tests/engine_equivalence.py) plus a
    both-ways key check: engine-vs-engine results must carry exactly the
    same keys (the harness's one-way check serves lane >= solo rows)."""
    assert set(a) == set(o)
    assert_results_match(a, o)


def test_paper_replay_engines_identical():
    """The flagship invariant: both engines consume the RNG identically
    and report matching totals for the paper replay at seed 2021."""
    res_a, ctl_a = run_solo(paper_spec(), 2021, engine="array")
    res_o, ctl_o = run_solo(paper_spec(), 2021, engine="object")
    _assert_results_match(res_a.to_dict(), res_o.to_dict())
    # the operational sequence (ramp, outage, budget cap) happens at the
    # same ticks; only within-tick $ snapshots in alert text may differ
    ev_a = [l for l in ctl_a.log if l.startswith("t=")]
    ev_o = [l for l in ctl_o.log if l.startswith("t=")]
    assert ev_a == ev_o
    # and the replay still reproduces the paper's numbers
    assert 14500 <= res_a["accel_days"] <= 17500
    assert 52000 <= res_a["cost"] <= 60000
    assert 2.7 <= res_a["eflop_hours_fp32"] <= 3.4


def test_engines_identical_with_scale_events():
    """Scale-up/down/deprovision mid-run: totals still match exactly."""
    results = {}
    for engine in ("array", "object"):
        cfg = SimConfig(duration_h=30.0, seed=7, engine=engine)
        sim = CloudSimulator(t4_catalog(), 1e6, cfg)
        sim.at(0.0, lambda s: s.prov.scale_to(250, s.now))
        sim.at(5.0, lambda s: s.prov.scale_to(1200, s.now))
        sim.at(12.0, lambda s: s.prov.deprovision_all(s.now))
        sim.at(14.0, lambda s: s.prov.scale_to(600, s.now))
        sim.run_until(30.0)
        results[engine] = sim.results()
    _assert_results_match(results["array"], results["object"])


def test_engines_identical_nat_storm():
    """Misconfigured lease (>= Azure's 240 s NAT timeout) causes the
    paper's preemption storm in both engines identically."""
    results = {}
    for engine in ("array", "object"):
        cfg = SimConfig(duration_h=10.0, seed=3, lease_interval_s=300.0,
                        engine=engine)
        sim = CloudSimulator(t4_catalog(), 1e6, cfg)
        sim.at(0.0, lambda s: s.prov.scale_to(300, s.now))
        sim.run_until(10.0)
        results[engine] = sim.results()
    _assert_results_match(results["array"], results["object"])
    assert results["array"]["nat_drops"] > 0


def test_array_engine_money_conservation():
    """charged $ == sum over instances of billed hours x group spot rate,
    including instances compacted out of the arrays mid-run."""
    cfg = SimConfig(duration_h=48.0, seed=11, overhead_per_day=0.0)
    sim = CloudSimulator(t4_catalog(), 1e9, cfg)
    sim.at(0.0, lambda s: s.prov.scale_to(1500, s.now))
    sim.at(20.0, lambda s: s.prov.scale_to(400, s.now))
    sim.run_until(48.0)
    sim.settle()
    eng = sim.fleet
    hours = eng.billed_hours_by_group()
    by_provider = {}
    for gi in range(eng.G):
        name = eng.g_provider[gi].name
        by_provider[name] = by_provider.get(name, 0.0) \
            + hours[gi] * eng.rate_h(gi)
    for name, dollars in by_provider.items():
        assert dollars == pytest.approx(
            sim.ledger.by_provider.get(name, 0.0), rel=1e-9, abs=1e-6)
    assert sum(by_provider.values()) == pytest.approx(sim.ledger.spent,
                                                      rel=1e-9)


def test_array_engine_compaction_bounds_memory():
    """High-churn run: the instance arrays track the live fleet, not
    every instance ever created."""
    cfg = SimConfig(duration_h=72.0, seed=5, overhead_per_day=0.0)
    sim = CloudSimulator(t4_catalog(), 1e9, cfg)
    sim.at(0.0, lambda s: s.prov.scale_to(2000, s.now))
    sim.run_until(72.0)
    eng = sim.fleet
    assert eng.retired_count > 0, "churn should have retired instances"
    total_created = eng.n + eng.retired_count
    assert eng.n < total_created   # arrays actually shrank
    # fleet held at target (final tick's preemptions are replaced at the
    # next tick's maintain, so allow that one tick of slack)
    assert 1950 <= eng.total_running() <= 2000


def test_heterogeneous_catalog_campaign():
    """The §III mixed pool is expressible: cheapest-$/day SKUs fill first
    and EFLOP accounting weights each provider's GPU peak."""
    cat = heterogeneous_catalog()
    cfg = SimConfig(duration_h=24.0, seed=2, overhead_per_day=0.0)
    sim = CloudSimulator(cat, 1e9, cfg)
    sim.at(0.0, lambda s: s.prov.scale_to(3000, s.now))
    sim.run_until(24.0)
    res = sim.results()
    # price priority: the $2.7/day azure-m60 and $2.9/day azure-t4 SKUs
    # fill before any V100 capacity
    assert res["by_provider"]["azure-m60"] > 0
    assert res["by_provider"]["azure-t4"] > 0
    # weighted EFLOP accounting != homogeneous formula (M60s drag it down)
    homog = res["busy_hours"] * cfg.accel_tflops * 1e12 / 1e18
    assert res["eflop_hours_fp32"] != pytest.approx(homog, rel=1e-3)
    assert res["eflop_hours_fp32"] > 0


def test_heterogeneous_engines_identical():
    results = {}
    for engine in ("array", "object"):
        cfg = SimConfig(duration_h=12.0, seed=13, engine=engine)
        sim = CloudSimulator(heterogeneous_catalog(), 1e8, cfg)
        sim.at(0.0, lambda s: s.prov.scale_to(2500, s.now))
        sim.run_until(12.0)
        results[engine] = sim.results()
    _assert_results_match(results["array"], results["object"])


def test_array_ce_facade_views():
    """The ce/prov facades answer the same questions as the seed objects."""
    cfg = SimConfig(duration_h=4.0, seed=9)
    sim = CloudSimulator(t4_catalog(), 1e6, cfg)
    sim.at(0.0, lambda s: s.prov.scale_to(100, s.now))
    sim.run_until(4.0)
    st = sim.ce.stats()
    assert st["pilots_live"] == 100
    assert st["pilots_busy"] == sum(sim.ce.busy_by_provider().values())
    assert len(sim.ce.queue) == st["queued"]
    live = list(sim.prov.live_instances())
    assert len(live) == 100
    assert all(i.alive for i in live)
    g0 = sim.prov.groups[0]
    assert g0.provider.name == "azure"       # cheapest first
    assert 0.0 < g0.utilization() <= 1.0


def test_facade_submit_preserves_job_identity():
    """ce.submit through the array facade keeps the Job's id and
    checkpointed progress, like the object CE."""
    cfg = SimConfig(duration_h=2.0, seed=1)
    sim = CloudSimulator(t4_catalog(), 1e6, cfg)
    sim.ce.submit(Job(id=777, wall_h=2.0, done_h=1.5, attempts=3))
    eng = sim.fleet
    assert eng.j_id[0] == 777
    assert eng.j_done[0] == 1.5
    assert eng.j_attempts[0] == 3
    assert eng.next_job_id() == 778    # counter advanced past it
    with pytest.raises(PermissionError):
        sim.ce.submit(Job(id=1, wall_h=1.0, policy="not-icecube"))
    # the 1.5h-done job needs only 0.5h on a pilot: give it one tick
    sim.prov.scale_to(1, 0.0)
    sim.run_until(0.5)
    assert len(sim.ce.finished) == 1


def test_all_instances_includes_compacted():
    """prov.all_instances() stays complete after compaction (the object
    engine's retired-list semantics): summed billed hours x rate must
    reproduce the ledger, counting compacted instances."""
    cfg = SimConfig(duration_h=72.0, seed=5, overhead_per_day=0.0)
    sim = CloudSimulator(t4_catalog(), 1e9, cfg)
    sim.at(0.0, lambda s: s.prov.scale_to(2000, s.now))
    sim.run_until(72.0)
    sim.settle()
    eng = sim.fleet
    assert eng.retired_count > 0
    insts = list(sim.prov.all_instances())
    assert len(insts) == eng.n + eng.retired_count
    rate = {g.provider.name: eng.rate_h(gi)
            for gi, g in enumerate(sim.prov.groups)}
    dollars = sum((i.last_charged - i.started_at) * rate[i.provider]
                  for i in insts)
    assert dollars == pytest.approx(
        sum(sim.ledger.by_provider.get(p, 0.0)
            for p in rate), rel=1e-9)


def test_custom_ramp_and_outage_spec():
    """Custom inline catalogs/ramps are expressible as a CampaignSpec and
    the outage + budget-cap machinery works outside the T4 replay."""
    spec = CampaignSpec(
        name="campaign", providers=tuple(heterogeneous_catalog().values()),
        budget=30000.0, duration_h=48.0,
        timeline=(SetTarget(0.0, 100), SetTarget(4.0, 2000),
                  CEOutage(252.0, 2.0, 1000)))
    res, ctl = run_solo(spec, 6, engine="array")
    res = res.to_dict()
    log = "\n".join(ctl.log)
    assert "scale_to(100)" in log and "scale_to(2000)" in log
    assert res["accel_hours"] > 0
    assert res["budget"]["overdraft"] == 0
    assert sum(res["by_provider"].values()) > 0
    # engine parameter honored
    res_o, _ = run_solo(spec, 6, engine="object")
    _assert_results_match(res, res_o.to_dict())


def test_job_ids_unique_across_requeues():
    """Seed bug: ensure_jobs derived IDs from queue+finished lengths,
    ignoring jobs attached to pilots -> collisions. Monotonic CE counter
    fixes it in both engines."""
    for engine in ("array", "object"):
        cfg = SimConfig(duration_h=12.0, seed=4, engine=engine)
        sim = CloudSimulator(t4_catalog(), 1e6, cfg)
        sim.at(0.0, lambda s: s.prov.scale_to(500, s.now))
        sim.run_until(12.0)
        if engine == "array":
            ids = sim.fleet.j_id[:sim.fleet.jn]
            assert len(np.unique(ids)) == len(ids)
        else:
            seen = [j.id for j in sim.ce.finished] \
                + [j.id for j in sim.ce.queue] \
                + [p.job.id for p in sim.ce.pilots.values()
                   if p.job is not None]
            assert len(set(seen)) == len(seen), engine
