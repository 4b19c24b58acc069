"""Control-plane invariants: budget conservation, overlay safety, the NAT
preemption storm, provisioner semantics, campaign reproduction of the
paper's published numbers, straggler policies. Property-based where the
invariant is over arbitrary event sequences (hypothesis)."""
import pytest

pytest.importorskip("hypothesis")  # property tests degrade gracefully
import hypothesis.strategies as st_

from hypothesis import given, settings

from repro.core.budget import BudgetLedger
from repro.core.overlay import ComputeElement, Job
from repro.core.provider import t4_catalog
from repro.core.provisioner import MultiCloudProvisioner
from repro.core.simulator import CloudSimulator, SimConfig
from repro.core.spec import ICECUBE_BASELINE_GPUH_PER_2W, paper_spec, run_solo
from repro.core.straggler import SpeculativeScheduler, StragglerMonitor


# --------------------------------------------------------------------------
# budget (CloudBank) — property tests
# --------------------------------------------------------------------------
@settings(max_examples=60, deadline=None)
@given(st_.lists(st_.tuples(st_.sampled_from(["azure", "gcp", "aws"]),
                            st_.floats(0, 500)), max_size=60),
       st_.floats(100, 10000))
def test_budget_conservation(charges, budget):
    led = BudgetLedger(budget)
    t = 0.0
    for prov, amt in charges:
        led.charge(prov, amt, t)
        t += 1.0
    assert abs(led.spent - sum(a for _, a in charges)) < 1e-6
    assert abs(led.spent - sum(led.by_provider.values())) < 1e-6
    assert led.remaining() >= 0
    assert abs((led.remaining() + min(led.spent, budget)) - budget) < 1e-6


@settings(max_examples=40, deadline=None)
@given(st_.lists(st_.floats(1, 300), min_size=1, max_size=80))
def test_budget_thresholds_fire_once_descending(amounts):
    led = BudgetLedger(1000.0)
    fired = []
    led.on_threshold(lambda frac, rem, rate: fired.append(frac))
    for i, a in enumerate(amounts):
        led.charge("azure", a, float(i))
    assert len(fired) == len(set(
        th for th in led.thresholds if led.remaining_fraction() <= th))
    assert fired == sorted(fired, reverse=True)


def test_budget_rejects_negative():
    led = BudgetLedger(100.0)
    with pytest.raises(ValueError):
        led.charge("azure", -1.0, 0.0)


# --------------------------------------------------------------------------
# overlay — property tests
# --------------------------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(st_.lists(st_.sampled_from(["submit", "pilot", "lose", "tick"]),
                 min_size=1, max_size=120),
       st_.integers(0, 2 ** 31 - 1))
def test_overlay_invariants(script, seed):
    import random
    rng = random.Random(seed)
    ce = ComputeElement(lease_interval_s=120.0)
    submitted = 0
    for op in script:
        if op == "submit":
            submitted += 1
            ce.submit(Job(submitted, wall_h=rng.choice([0.5, 1.0, 2.0])))
        elif op == "pilot":
            ce.register_pilot(rng.randrange(1000), "azure", 240.0, 0.0)
        elif op == "lose" and ce.pilots:
            ce.pilot_lost(rng.choice(list(ce.pilots)), 0.0)
        elif op == "tick":
            ce.match(0.0)
            ce.advance(0.5, 0.0)
        # invariant: jobs are never lost
        running = sum(1 for p in ce.pilots.values() if p.job is not None)
        assert len(ce.queue) + running + len(ce.finished) == submitted
        # invariant: no job on a dead pilot
        assert not any(p.dead and p.job for p in ce.pilots.values())
        # invariant: a job sits on at most one pilot
        jobs = [id(p.job) for p in ce.pilots.values() if p.job]
        assert len(jobs) == len(set(jobs))


def test_nat_timeout_preemption_storm():
    """The paper's Azure bug: OSG's 5-min keepalive vs Azure's 4-min NAT
    timeout caused 'constant preemption of the user jobs'; fixed by tuning
    the interval below the timeout."""
    broken = ComputeElement(lease_interval_s=300.0)   # OSG default
    broken.submit(Job(1, wall_h=10.0))
    broken.register_pilot(1, "azure", nat_timeout_s=240.0, now_h=0.0)
    broken.match(0.0)
    broken.advance(0.25, 0.25)
    assert broken.nat_drop_events == 1                # job got preempted
    assert len(broken.queue) == 1                     # ... and requeued

    fixed = ComputeElement(lease_interval_s=120.0)    # the paper's fix
    fixed.submit(Job(1, wall_h=0.5))
    fixed.register_pilot(1, "azure", nat_timeout_s=240.0, now_h=0.0)
    fixed.match(0.0)
    fixed.advance(0.5, 0.5)
    assert fixed.nat_drop_events == 0
    assert len(fixed.finished) == 1


def test_ce_policy_rejects_foreign_jobs():
    ce = ComputeElement(accept_policy="icecube")
    with pytest.raises(PermissionError):
        ce.submit(Job(1, wall_h=1.0, policy="atlas"))


# --------------------------------------------------------------------------
# provisioner
# --------------------------------------------------------------------------
def test_provisioner_price_priority_and_capacity():
    prov = MultiCloudProvisioner(t4_catalog(), BudgetLedger(1e6))
    got = prov.scale_to(800, now=0.0)
    assert got == 800
    by = prov.running_by_provider()
    assert by["azure"] == 800                 # cheapest filled first
    prov.scale_to(1500, now=1.0)
    by = prov.running_by_provider()
    assert by["azure"] == 1200                # azure capacity exhausted
    assert by["gcp"] + by["aws"] == 300
    prov.deprovision_all(now=2.0)
    assert prov.total_running() == 0


def test_provisioner_bills_ledger():
    led = BudgetLedger(1e6)
    prov = MultiCloudProvisioner(t4_catalog(), led)
    prov.scale_to(100, now=0.0)
    prov.bill(now=24.0)                       # one day at $2.9/day
    assert abs(led.spent - 100 * 2.9) < 1.0


# --------------------------------------------------------------------------
# campaign — reproduces the paper's published numbers
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def campaign():
    return run_solo(paper_spec(), 2021)


def test_campaign_gpu_days(campaign):
    res, _ = campaign
    assert 14500 <= res["accel_days"] <= 17500          # paper: ~16k

def test_campaign_cost(campaign):
    res, _ = campaign
    assert 52000 <= res["cost"] <= 60000                # paper: ~$58k
    assert res["budget"]["overdraft"] == 0

def test_campaign_eflop_hours(campaign):
    res, _ = campaign
    assert 2.7 <= res["eflop_hours_fp32"] <= 3.4        # paper: ~3.1

def test_campaign_doubling(campaign):
    res, _ = campaign
    factor = 1 + res["busy_hours"] / ICECUBE_BASELINE_GPUH_PER_2W
    assert 1.8 <= factor <= 2.4                         # "approx doubling"

def test_campaign_outage_and_budget_cap(campaign):
    _, ctl = campaign
    log = "\n".join(ctl.log)
    assert "CE OUTAGE" in log and "resume at 1000" in log
    assert "budget floor hit" in log


def test_outage_costs_little():
    """De-provisioning during the outage keeps burn near zero."""
    cfg = SimConfig(duration_h=6.0)
    sim = CloudSimulator(t4_catalog(), 1e6, cfg)
    sim.prov.scale_to(500, 0.0)
    sim.run_until(2.0)
    sim.prov.deprovision_all(sim.now)
    sim.prov.bill(sim.now)           # settle the final partial hour
    spent_before = sim.ledger.spent
    sim.run_until(6.0)
    idle_burn = sim.ledger.spent - spent_before
    assert idle_burn <= cfg.overhead_per_day * 4 / 24 + 1e-6


# --------------------------------------------------------------------------
# stragglers
# --------------------------------------------------------------------------
def test_speculative_scheduler():
    s = SpeculativeScheduler(spec_factor=2.0, min_samples=3)
    for t in (1.0, 1.1, 0.9, 1.0):
        s.record_completion(t)
    assert not s.should_speculate(1.5)
    assert s.should_speculate(2.5)
    assert s.speculated == 1


def test_straggler_monitor_evicts_slow_pod():
    m = StragglerMonitor(evict_factor=1.5, min_steps=5)
    for i in range(20):
        for pod in ("a", "b", "c", "d"):
            m.record(pod, 1.0 if pod != "d" else 2.5)
    assert m.stragglers() == ["d"]
    m.evict("d")
    assert m.stragglers() == []
