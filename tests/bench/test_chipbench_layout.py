"""The chip benchmark's files: every cell resolves by name, the names
keep to the benchmark's rules, the configurations are the repository's
specs, the traffic generator gives each cell's width, and the harness
refuses a machine without a TPU."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import cells, traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
WIDTH = {"paper.grid-1020": 1020, "paper.seeds-32": 32}


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_benchmark_file_keeps_the_rules():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "benchmarks/chip/run.py"]
    assert all(_line(w) for w in BENCH["command"])
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert (ROOT / p).is_dir() and ".." not in p
    assert 1 <= BENCH["run_seconds"] <= 51
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics] + CELLS \
        + [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and _line(m["layer"])
        assert set(m["workloads"]) <= set(CELLS)
    for w in BENCH["workloads"]:
        assert w["chips"] == 1 and _line(w["why"])
    for c in BENCH["configs"]:
        assert _line(c["why"]) and _line(c["source"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_to_its_files(workload):
    c = cells.resolve(workload)
    assert c["config"]["spec"]["schema_version"] == 1
    assert c["traffic"]["loop"] == "closed"
    for m in c["end_to_end"] + c["per_layer"]:
        assert callable(cells.load_module("metrics", m["name"]).read)
    assert {"setup_s", "campaigns_per_s"} <= {m["name"]
                                              for m in c["end_to_end"]}
    assert c["per_layer"]
    for k in ("lanes_off", "worst_gap", "mean_gap"):
        assert c["limits"][k]["limit"] >= 0


@pytest.mark.parametrize("name,golden", [
    ("paper", "paper_replay.spec.json")])
def test_config_spec_round_trips(name, golden):
    from repro.core.spec import CampaignSpec
    spec = cells.load_json("configs", name)["spec"]
    got = CampaignSpec.from_json(json.dumps(spec))
    assert json.loads(got.to_json()) == spec
    assert spec == json.loads((ROOT / "tests" / "data" / golden)
                              .read_text())


def test_config_catalog_is_the_t4_catalog():
    from dataclasses import asdict

    from repro.core.provider import t4_catalog
    want = []
    for p in t4_catalog().values():
        d = asdict(p)
        if d["nat_idle_timeout_s"] == float("inf"):
            d["nat_idle_timeout_s"] = None
        want.append(d)
    tab = cells.load_json("configs", "paper")["catalog_tables"]["t4"]
    assert json.loads(json.dumps(want)) == tab


@pytest.mark.parametrize("workload", CELLS)
def test_traffic_width_and_distinct_seeds(workload):
    c = cells.resolve(workload)
    assert traffic.lanes_per_call(c["config"], c["traffic"]) \
        == WIDTH[workload]
    sched = traffic.SeedSchedule(2 ** 33 + 7, c["traffic"]["seeds_per_call"])
    calls = [sched.next_call() for _ in range(5)]
    flat = [s for call in calls for s in call]
    assert len(set(flat)) == len(flat)
    assert all(0 <= s < 2 ** 31 for s in flat)
    again = traffic.SeedSchedule(2 ** 33 + 7, c["traffic"]["seeds_per_call"])
    assert again.next_call() == calls[0]


def test_grid_variants_follow_the_axes():
    c = cells.resolve("paper.grid-1020")
    specs = traffic.variants(c["config"], c["traffic"])
    assert len(specs) == 60 and len({s["name"] for s in specs}) == 60
    assert (specs[0]["price_scale"], specs[0]["budget_floor_fraction"],
            specs[0]["budget"]) == (0.8, 0.1, 40000.0)
    assert (specs[-1]["price_scale"], specs[-1]["budget_floor_fraction"],
            specs[-1]["budget"]) == (1.25, 0.4, 80000.0)


def test_chip_lookup_refuses_a_cpu_device():
    from benchmarks.chip import bench
    with pytest.raises(bench.NoChip, match="TPU"):
        bench.chips(1)


def test_harness_refuses_a_cpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/chip/run.py"),
         "--workload", "paper.seeds-32", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=str(ROOT), timeout=120)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and p.stdout.strip() == ""
    assert "TPU" in p.stderr
