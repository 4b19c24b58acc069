"""CampaignSpec / api.run contract tests (deterministic; a hypothesis
round-trip + equivalence property rides along in
tests/test_spec_properties.py where hypothesis is installed):

  * JSON round-trip is lossless, including inline provider catalogs and
    every timeline event kind,
  * the committed golden spec (tests/data/paper_replay.spec.json) equals
    paper_spec() and reproduces the seed-2021 replay totals bit-for-bit
    through the run() front door,
  * randomized specs — including the new timed PriceShift / BudgetFloor /
    CapacityShift events — run bit-identically solo vs batched, with
    matching events_fired provenance,
  * SweepResult.to_csv is deterministic and row-ordered.
"""
import json
import os

import pytest

from repro.core.api import run, sweep as api_sweep
from repro.core.provider import t4_catalog
from repro.core.spec import (BudgetFloor, CampaignResult, CampaignSpec,
                             CapacityShift, CEOutage, GpuSlicing,
                             PriceCurve, PriceShift, SetTarget, paper_spec,
                             run_solo)
from tests.engine_equivalence import (assert_engines_equivalent,
                                      assert_results_match,
                                      assert_sweep_equivalent)

# migrated call sites keep the historical underscore name
_assert_results_match = assert_results_match

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "paper_replay.spec.json")

# seed-2021 paper-replay totals (pinned; must never drift)
PAPER_2021 = {"cost": 56936.43, "accel_days": 16407.9,
              "eflop_hours_fp32": 3.007, "preemptions": 3716,
              "jobs_finished": 97852}


# -- serialization ---------------------------------------------------------

def test_json_roundtrip_every_event_kind_and_inline_catalog():
    spec = CampaignSpec(
        name="kitchen-sink", catalog="heterogeneous",
        providers=tuple(t4_catalog().values()),   # inline catalog wins
        capacity_scale=0.5, spot=False, ondemand_fraction=0.25,
        price_scale=1.25, budget=12345.67, budget_floor_fraction=0.25,
        downscale_target=321, duration_h=48.0, dt_h=0.25,
        lease_interval_s=90.0, job_wall_h=3.0, job_checkpoint_h=0.5,
        min_queue=1234, overhead_per_day=10.0, accel_tflops=7.5,
        gpu_slicing=GpuSlicing(slices=4, providers=("azure", "gcp"),
                               price_factor=1.1, tflops_factor=0.95),
        timeline=(SetTarget(0.0, 100), PriceShift(6.0, 1.3),
                  CapacityShift(12.0, 0.5), BudgetFloor(18.0, 0.1, 50),
                  CEOutage(24.0, 3.0, 77), SetTarget(30.0, 200),
                  PriceCurve(((32.0, 1.2), (40.0, 0.8))),
                  PriceCurve(((36.0, 1.5),), provider="azure/4")))
    again = CampaignSpec.from_json(spec.to_json())
    assert again == spec
    # and the dict form is pure JSON (no dataclasses smuggled through)
    d = json.loads(spec.to_json())
    assert d["timeline"][1] \
        == {"kind": "price_shift", "at_h": 6.0, "factor": 1.3}
    assert d["timeline"][6] == {"kind": "price_curve", "provider": None,
                                "points": [[32.0, 1.2], [40.0, 0.8]]}
    assert d["gpu_slicing"]["slices"] == 4
    assert again.gpu_slicing.providers == ("azure", "gcp")
    assert again.timeline[6].points == ((32.0, 1.2), (40.0, 0.8))


def test_inline_catalog_json_is_strict_json():
    """nat_idle_timeout_s defaults to inf; the serialized spec must still
    be standard JSON (no Python-only Infinity tokens) and round-trip."""
    spec = CampaignSpec(name="inline",
                        providers=tuple(t4_catalog().values()))
    text = spec.to_json()
    assert "Infinity" not in text
    # strict parse: reject non-standard constants outright
    strict = json.loads(text, parse_constant=lambda c: (_ for _ in ()
                                                        ).throw(
                            ValueError(c)))
    assert strict["providers"][1]["nat_idle_timeout_s"] is None
    again = CampaignSpec.from_json(text)
    assert again == spec
    assert again.providers[1].nat_idle_timeout_s == float("inf")


def test_run_treats_string_seed_as_one_seed():
    """seeds="2021" must not become the per-character sweep [2,0,2,1]."""
    spec = CampaignSpec(name="strseed", duration_h=12.0, budget=2000.0,
                        timeline=(SetTarget(0.0, 50),))
    res = run(spec, seeds="7")
    assert isinstance(res, CampaignResult)
    assert res.seed == 7


def test_from_json_rejects_unknowns():
    with pytest.raises(ValueError):
        CampaignSpec.from_dict({"schema_version": 99})
    with pytest.raises(ValueError):
        CampaignSpec.from_dict({"no_such_field": 1})
    with pytest.raises(ValueError):
        CampaignSpec.from_dict(
            {"timeline": [{"kind": "warp_drive", "at_h": 0.0}]})


def test_golden_paper_spec_file_is_current():
    with open(GOLDEN) as f:
        assert CampaignSpec.from_json(f.read()) == paper_spec()


# -- the flagship invariant: golden spec -> paper totals -------------------

@pytest.fixture(scope="module")
def paper_result():
    with open(GOLDEN) as f:
        spec = CampaignSpec.from_json(f.read())
    return run(spec, seeds=[2021])


def test_run_paper_spec_reproduces_pinned_totals(paper_result):
    res = paper_result
    assert isinstance(res, CampaignResult)
    for k, v in PAPER_2021.items():
        assert res[k] == v, k
    # typed accessors agree with the legacy mapping facade
    assert res.cost == res["cost"]
    assert res.to_dict()["budget"]["overdraft"] == 0
    cmp = res.compare_paper()
    assert abs(cmp["cost"]["err_pct"]) < 15
    assert 1.8 <= res.doubling_factor() <= 2.4
    # provenance: the full operational sequence was recorded
    events = [e["event"] for e in res.events_fired]
    assert events == ["scale"] * 6 + ["outage_on", "outage_off",
                                      "budget_floor"]
    assert any("budget floor hit" in line for line in res.log)
    assert len(res.history) == 336 * 4


# -- randomized specs: solo == batched, including the new event kinds ------

def _random_specs():
    """A deliberately gnarly mix of catalogs, mixes and timed events.
    Floors sit on ledger-threshold values so the cap tick is
    engine-order independent."""
    return [
        CampaignSpec(
            name="shifty", duration_h=36.0, budget=9000.0,
            budget_floor_fraction=0.25, downscale_target=150,
            timeline=(SetTarget(0.0, 300), PriceShift(6.0, 1.4),
                      CapacityShift(10.0, 0.4), SetTarget(18.0, 500),
                      PriceShift(24.0, 0.7))),
        CampaignSpec(
            name="floor-rearm", duration_h=36.0, budget=6000.0,
            budget_floor_fraction=0.1, downscale_target=50,
            timeline=(SetTarget(0.0, 400), BudgetFloor(8.0, 0.5, 120),
                      SetTarget(12.0, 600), CEOutage(20.0, 4.0, 250))),
        CampaignSpec(
            name="hetero-squeeze", catalog="heterogeneous",
            duration_h=30.0, budget=40000.0, min_queue=6000,
            timeline=(SetTarget(0.0, 2500), CapacityShift(8.0, 0.3),
                      CapacityShift(16.0, 2.0), PriceShift(12.0, 1.1))),
        CampaignSpec(
            name="od-mix", ondemand_fraction=0.25, price_scale=0.9,
            duration_h=30.0, budget=15000.0,
            timeline=(SetTarget(0.0, 800), PriceShift(10.0, 2.0),
                      SetTarget(20.0, 200))),
        CampaignSpec(
            name="ondemand-storm", spot=False, duration_h=24.0,
            budget=30000.0, lease_interval_s=300.0,  # NAT-drop regime
            timeline=(SetTarget(0.0, 350), CEOutage(10.0, 2.0, 300))),
    ]


@pytest.mark.parametrize("spec", _random_specs(),
                         ids=lambda s: s.name)
def test_solo_vs_batched_bit_identical(spec):
    ref = assert_engines_equivalent(spec, 13, engines=("batched",))
    # the spec actually exercised its timeline
    assert len(ref.events_fired) >= len(spec.timeline)


def test_mixed_spec_sweep_batched_matches_sequential():
    """All the gnarly specs in ONE sweep call: lanes group into
    structurally-compatible engines and every row still matches the
    sequential reference, events_fired included."""
    specs = _random_specs()
    batched = assert_sweep_equivalent(specs, [3, 13])
    for row in batched.rows:
        assert row["events_fired"], "provenance must not be empty"


def test_sequential_sweep_carries_events_fired():
    """Regression (satellite): the sequential engine used to discard the
    per-lane controller provenance; both engines now record it."""
    spec = CampaignSpec(name="tiny", duration_h=24.0, budget=3000.0,
                        timeline=(SetTarget(0.0, 120),
                                  CEOutage(6.0, 2.0, 80)))
    for engine in ("batched", "sequential"):
        sw = api_sweep([spec], [5], engine=engine)
        (row,) = sw.rows
        kinds = [e["event"] for e in row["events_fired"]]
        assert kinds[:2] == ["scale", "outage_on"], engine
        assert "outage_off" in kinds, engine


# -- price/capacity shifts actually bite -----------------------------------

def test_price_shift_charges_more():
    base = CampaignSpec(name="flat", duration_h=24.0, budget=1e9,
                        overhead_per_day=0.0,
                        timeline=(SetTarget(0.0, 200),))
    shifted = CampaignSpec(name="spike", duration_h=24.0, budget=1e9,
                           overhead_per_day=0.0,
                           timeline=(SetTarget(0.0, 200),
                                     PriceShift(12.0, 3.0)))
    r0 = run(base, seeds=2)
    r1 = run(shifted, seeds=2)
    # 12h at 1x + 12h at 3x => roughly 2x the flat bill
    assert 1.7 * r0.cost < r1.cost < 2.3 * r0.cost
    assert r1.accel_hours == r0.accel_hours   # fleet behavior unchanged


def test_capacity_shift_limits_refill_without_evicting():
    spec = CampaignSpec(name="shrink", duration_h=24.0, budget=1e9,
                        timeline=(SetTarget(0.0, 1000),
                                  CapacityShift(8.0, 0.1)))
    res, ctl = run_solo(spec, 4)
    running = [t.running for t in res.history]
    assert max(running[:32]) >= 990         # filled before the shift
    # capacity shrink does not evict: fleet persists above the new cap
    assert running[33] > 500
    assert ctl.sim.prov.groups[0].region.capacity \
        == max(1, int(500 * 0.1))


# -- CSV artifact ----------------------------------------------------------

def test_sweep_csv_deterministic_and_sorted(tmp_path):
    specs = [CampaignSpec(name="b", duration_h=24.0, budget=4000.0,
                          timeline=(SetTarget(0.0, 100),)),
             CampaignSpec(name="a", duration_h=24.0, budget=4000.0,
                          timeline=(SetTarget(0.0, 150),))]
    sw = api_sweep(specs, [2, 1], engine="batched")
    text = sw.to_csv()
    assert text == sw.to_csv()              # byte-deterministic
    lines = text.strip().split("\n")
    assert lines[0].startswith("scenario,seed,")
    assert "budget.total_spent" in lines[0]
    assert "events_fired" in lines[0]
    # rows sorted by (scenario, seed) regardless of input order
    keys = [tuple(line.split(",")[:2]) for line in lines[1:]]
    assert keys == [("a", "1"), ("a", "2"), ("b", "1"), ("b", "2")]
    out = tmp_path / "sweep.csv"
    sw.to_csv(str(out))
    assert out.read_text() == text


# -- CLI -------------------------------------------------------------------

def test_campaigns_cli_run_and_show(tmp_path, capsys):
    from repro import campaigns as cli
    spec = CampaignSpec(name="cli-smoke", duration_h=12.0, budget=2000.0,
                        timeline=(SetTarget(0.0, 80),))
    spec_path = tmp_path / "smoke.spec.json"
    spec_path.write_text(spec.to_json())
    out_json = tmp_path / "out.json"
    assert cli.main(["run", str(spec_path), "--seeds", "3",
                     "--json", str(out_json)]) == 0
    payload = json.loads(out_json.read_text())
    assert payload["kind"] == "campaign"
    assert payload["results"]["cost"] > 0
    assert payload["spec"]["name"] == "cli-smoke"
    # sweep path + csv artifact
    out_csv = tmp_path / "out.csv"
    assert cli.main(["run", str(spec_path), "--seeds", "3,4",
                     "--csv", str(out_csv)]) == 0
    assert out_csv.read_text().startswith("scenario,seed,")
    assert cli.main(["show", str(spec_path)]) == 0
    assert "cli-smoke" in capsys.readouterr().out


def test_campaigns_cli_paper_emits_golden(tmp_path):
    from repro import campaigns as cli
    out = tmp_path / "paper.spec.json"
    assert cli.main(["paper", "--out", str(out)]) == 0
    assert out.read_text() == open(GOLDEN).read()


def test_campaigns_cli_lint(tmp_path, capsys):
    from repro import campaigns as cli
    good = tmp_path / "good.spec.json"
    good.write_text(paper_spec().to_json())
    assert cli.main(["lint", str(good)]) == 0
    assert "OK" in capsys.readouterr().out
    # a spec with unsorted/duplicate times, a negative target, a bad
    # catalog and a bogus curve provider lints dirty (exit 1), listing
    # every finding at once
    bad = CampaignSpec(
        name="bad", catalog="t4", budget=1000.0, duration_h=24.0,
        timeline=(SetTarget(12.0, 100), SetTarget(6.0, -5),
                  SetTarget(6.0, 7),
                  PriceCurve(((3.0, -2.0),), provider="warp-cloud")))
    bad_path = tmp_path / "bad.spec.json"
    bad_path.write_text(bad.to_json())
    assert cli.main(["lint", str(bad_path)]) == 1
    out = capsys.readouterr().out
    assert "not sorted" in out
    assert "negative target" in out
    assert "non-positive price factor" in out
    assert "unknown provider 'warp-cloud'" in out
    assert "share t=6.0" in out
    # unloadable file: reported, nonzero exit
    mangled = tmp_path / "mangled.spec.json"
    mangled.write_text("{\"no_such_field\": 1}")
    assert cli.main(["lint", str(mangled), str(good)]) == 1
    out = capsys.readouterr().out
    assert "cannot load spec" in out
    assert "OK" in out                    # the good file still lints


def test_campaigns_cli_lint_unknown_catalog(tmp_path, capsys):
    spec_d = CampaignSpec(name="x", duration_h=12.0,
                          timeline=()).to_dict()
    spec_d["catalog"] = "no-such-cloud"
    p = tmp_path / "cat.spec.json"
    p.write_text(json.dumps(spec_d))
    from repro import campaigns as cli
    assert cli.main(["lint", str(p)]) == 1
    assert "unknown catalog name" in capsys.readouterr().out


# -- float seeds are rejected, not truncated --------------------------------

def test_run_rejects_float_seeds():
    """Regression: seeds=2021.7 used to truncate to 2021 via int() and
    silently run a different campaign."""
    spec = CampaignSpec(name="floaty", duration_h=12.0, budget=2000.0,
                        timeline=(SetTarget(0.0, 50),))
    with pytest.raises(TypeError, match="silently truncated"):
        run(spec, seeds=2021.7)
    with pytest.raises(TypeError, match="integers"):
        run(spec, seeds=[3, 4.5])
    with pytest.raises(TypeError):
        run(spec, seeds=3.0)              # integral floats too: be strict
    import numpy as np
    with pytest.raises(TypeError):
        run(spec, seeds=np.float64(3))
    with pytest.raises(TypeError):
        api_sweep([spec, spec], [1.5, 2], engine="batched")
    with pytest.raises(TypeError):
        api_sweep([spec], [2.5])
    # and the SimConfig derivation itself is guarded
    from repro.core.simulator import SimConfig
    with pytest.raises(TypeError):
        SimConfig.from_spec(spec, 7.2)
    # ints (and numpy ints) still work
    assert run(spec, seeds=np.int64(5)).seed == 5


