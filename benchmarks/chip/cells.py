"""Find a cell's files by the names in ``BENCHMARK.json``."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_json(kind: str, name: str) -> dict:
    """``<kind>/<name>.json`` under this directory (configs, traffic,
    limits)."""
    return json.loads((HERE / kind / f"{name}.json").read_text())


def load_module(kind: str, name: str) -> ModuleType:
    """``<kind>/<name>.py`` as a module (metric readers, kernels); the
    name may hold ``.`` or ``-``, so it is loaded by path."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernel_modules() -> list:
    """Every tick kernel's description, by file name."""
    return [load_module("kernels", p.stem)
            for p in sorted((HERE / "kernels").glob("*.py"))]


def resolve(workload: str, bench: dict = None) -> dict:
    """The cell's entry, its configuration, its traffic mix, its
    metrics by kind, and its limits."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {
        "cell": cell,
        "config": load_json("configs", cell["config"]),
        "config_entry": conf,
        "traffic": load_json("traffic", cell["traffic"]),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
        "limits": load_json("limits", workload),
    }
