"""The comparison that decides ``correct``: the plain reference agrees
with the program, and a run driven through the harness (its look for a
chip skipped, at a size a test can hold) comes out not correct with the
control in the program's place and with each fault that a sweep cell
can have planted under the timed path.  The cell's own limits apply.
The NAT-drop program (``nat_any``) is driven through a ``paper`` cell
whose lease interval outlives Azure's NAT idle timeout."""
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import bench, cells, correct, reference  # noqa: E402

SEED = 2 ** 31 + 2021        # wider than 32 signed bits, as the driver's
RESOLVE = cells.resolve      # the cells at full size
#: each grid axis cut to its first two values, so a test run holds it
GRID_AXES = 2
#: OSG's default 5-minute keepalive, past Azure's 4-minute NAT timeout
NAT_LEASE_S = 300.0
#: calls that a sound run's window holds at the least
MIN_CALLS = 2


def small(workload, seeds_per_call=4, check_lanes=8):
    c = RESOLVE(workload)
    grid = {k: v[:GRID_AXES]
            for k, v in c["traffic"].get("grid", {}).items()}
    c["traffic"] = dict(c["traffic"], grid=grid,
                        seeds_per_call=seeds_per_call,
                        check_lanes=check_lanes)
    return c


def nat_spec(spec):
    return dict(spec, lease_interval_s=NAT_LEASE_S)


def small_nat(workload):
    """The cell with the paper's pre-fix keepalive in its spec."""
    c = small(workload)
    c["config"] = dict(c["config"], spec=nat_spec(c["config"]["spec"]))
    return c


def drive(workload="paper.seeds-32"):
    return bench.run_cell(workload, SEED, 0.2, False, time.perf_counter())


@pytest.fixture
def nat_cell(monkeypatch):
    monkeypatch.setattr(cells, "resolve", small_nat)


@pytest.fixture
def min_calls(monkeypatch):
    """The timed window runs on past its close until it holds
    ``MIN_CALLS`` calls, however slow a call is on a loaded machine."""
    loop = bench.Cell._loop

    def loop_on(self, schedule, more, record, timer):
        return loop(self, schedule,
                    lambda done: more(done) or len(done) < MIN_CALLS,
                    record, timer)
    monkeypatch.setattr(bench.Cell, "_loop", loop_on)


@pytest.fixture(autouse=True)
def small_cell_on_cpu(monkeypatch):
    """A driven run skips the harness's look for a chip and runs its
    cell at a size a test can hold."""
    import jax
    monkeypatch.setattr(bench, "chips", lambda n: jax.devices())
    monkeypatch.setattr(cells, "resolve", small)


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """A driven run turns JAX's persistent cache on; here it stays off,
    and the process's compile settings are as they were after."""
    import jax
    from repro import compile_cache
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "off")
    keep = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", keep)


@pytest.fixture
def fresh_scan():
    from repro.core import sweep_jax
    sweep_jax._scan_campaigns.clear_cache()
    yield
    sweep_jax._scan_campaigns.clear_cache()


@pytest.mark.parametrize("workload", ["paper.seeds-32", "paper.grid-1020"])
def test_sound_run_is_correct(workload, min_calls):
    out = drive(workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 8
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {m["name"] for m in
                                   RESOLVE(workload)["end_to_end"]}


def _control(lane_specs, use_pallas=None):
    """The reference in bfloat16, put in the program's place."""
    import ml_dtypes
    tables = cells.load_json("configs", "paper")["catalog_tables"]
    rows = reference.reference_rows(
        [(json_spec(s), seed) for s, seed in lane_specs], tables,
        ml_dtypes.bfloat16)
    return [({k: v for k, v in r.items() if k != "events_fired"},
             r["events_fired"], None) for r in rows]


def json_spec(spec):
    import json
    return json.loads(spec.to_json())


def test_control_is_not_correct(monkeypatch):
    from repro.core import sweep_jax
    monkeypatch.setattr(sweep_jax, "run_jax_detailed", _control)
    out = drive()
    assert not out["correct"]
    assert out["checks"]["lanes_off"]["value"] == 1.0


def test_state_left_unchanged_is_not_correct(monkeypatch, fresh_scan):
    import jax
    monkeypatch.setattr(jax.lax, "scan", lambda f, init, xs: (init, None))
    assert not drive()["correct"]


def test_half_the_lanes_left_out_is_not_correct(monkeypatch):
    from repro.core import sweep_jax
    run = sweep_jax.run_jax_detailed

    def half(lane_specs, use_pallas=None):
        n = max(1, len(lane_specs) // 2)
        done = run(lane_specs[:n], use_pallas)
        return (done * 2)[:len(lane_specs)]
    monkeypatch.setattr(sweep_jax, "run_jax_detailed", half)
    out = drive()
    assert not out["correct"]
    assert out["checks"]["lanes_off"]["value"] >= 0.25


def test_answer_altered_where_produced_is_not_correct(monkeypatch):
    from repro.core import sweep_jax
    results = sweep_jax.JaxSweepEngine.lane_results

    def altered(self, b):
        res = results(self, b)
        res["jobs_finished"] += 1
        return res
    monkeypatch.setattr(sweep_jax.JaxSweepEngine, "lane_results", altered)
    out = drive()
    assert not out["correct"]
    assert out["checks"]["lanes_off"]["value"] == 1.0


def test_sound_nat_run_is_correct(nat_cell, min_calls):
    out = drive()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 8


def test_nat_phase_removed_is_not_correct(monkeypatch, nat_cell):
    from repro.core import sweep_jax
    scan_call = sweep_jax.JaxSweepEngine._scan_call

    def no_nat(self):
        args, kw = scan_call(self)
        return args, dict(kw, nat_any=False)
    monkeypatch.setattr(sweep_jax.JaxSweepEngine, "_scan_call", no_nat)
    out = drive()
    assert not out["correct"]
    assert out["checks"]["lanes_off"]["value"] == 1.0


def test_nat_drops_counted_as_preemptions_only_is_not_correct(
        monkeypatch, nat_cell):
    from repro.core import sweep_jax
    results = sweep_jax.JaxSweepEngine.lane_results

    def as_preemptions(self, b):
        res = results(self, b)
        res["nat_drops"] = 0
        return res
    monkeypatch.setattr(sweep_jax.JaxSweepEngine, "lane_results",
                        as_preemptions)
    out = drive()
    assert not out["correct"]
    assert out["checks"]["lanes_off"]["value"] == 1.0


def _lanes(spec_source):
    if spec_source in ("paper", "paper-nat"):
        spec = cells.load_json("configs", "paper")["spec"]
        if spec_source == "paper-nat":
            spec = nat_spec(spec)
    else:
        spec = json.loads((ROOT / "tests" / "data" / spec_source)
                          .read_text())
    return [(spec, s) for s in (3, 2 ** 31 - 1, 12345)]


TABLES = cells.load_json("configs", "paper")["catalog_tables"]
LIMITS = RESOLVE("paper.seeds-32")["limits"]


@pytest.mark.parametrize("spec_source",
                         ["paper", "dataplane.spec.json", "paper-nat"])
def test_control_reference_in_lower_precision_differs(spec_source):
    import ml_dtypes
    lanes = _lanes(spec_source)
    want = reference.reference_rows(lanes, TABLES)
    ctl = reference.reference_rows(lanes, TABLES, ml_dtypes.bfloat16)
    nums = correct.numbers(ctl, want)
    assert not correct.judge(nums, LIMITS)[0]
    assert np.isfinite(correct.numbers(want, want)["worst_gap"])


@pytest.mark.parametrize("spec_source", ["dataplane.spec.json",
                                         "paper-nat"])
def test_reference_matches_the_program_on_the_variant(spec_source):
    """The reference's paths kept for configurations that no cell runs
    yet agree with the program: the stage-in path (the ``dp_gating`` +
    ``dp_staging`` variant) on the repository's data-plane spec, and the
    NAT-drop phase (the ``nat_any`` variant) on the paper's spec with its
    pre-fix keepalive, where every lane drops pilots."""
    from repro.core import api
    from repro.core.spec import CampaignSpec
    lanes = _lanes(spec_source)
    spec = CampaignSpec.from_dict(lanes[0][0])
    rows = api.sweep([spec], [s for _spec, s in lanes], engine="jax").rows
    want = reference.reference_rows(lanes, TABLES)
    ok, checks = correct.judge(
        correct.numbers([correct.slim(r) for r in rows], want), LIMITS)
    assert ok, checks
    if spec_source == "paper-nat":
        assert all(r["nat_drops"] > 0 for r in want + rows)


def test_reference_rows_of_plain_lanes_are_pinned():
    """Lanes with no NAT-dropping group step as they did before the
    reference learned the NAT phase: their rows are byte for byte the
    pinned ones, with ``nat_drops`` 0 beside them."""
    pinned = json.loads((ROOT / "tests" / "bench"
                         / "paper_reference_rows.json").read_text())
    spec = cells.load_json("configs", "paper")["spec"]
    rows = reference.reference_rows(
        [(spec, s) for s in pinned["seeds"]], TABLES)
    assert [r.pop("nat_drops") for r in rows] == [0] * len(rows)
    assert [json.dumps(r) for r in rows] == \
        [json.dumps(r) for r in pinned["rows"]]
