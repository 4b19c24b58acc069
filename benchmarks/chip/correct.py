"""Whether the timed calls returned the right answers.

After the window closes, ``check_lanes`` lanes are drawn from the seed
among every lane that the window's calls returned.  The plain reference
(``reference.py``) runs each drawn lane from its spec's JSON and seed,
and three numbers compare the rows:

* ``lanes_off``: the share of drawn lanes whose row differs from the
  reference's anywhere: a count (jobs, preemptions, NAT drops, live
  instances per provider), the ``events_fired`` provenance, or a float
  total by more than ``FLOAT_RTOL``;
* ``worst_gap``: the widest relative gap of one lane's cost, GPU-days,
  jobs, preemptions, NAT drops or egress from the reference's;
* ``mean_gap``: the widest relative gap of the same totals' means over
  the drawn lanes.

Each has the limit in ``limits/<workload>.json``; a run is correct when
every number is at or under its limit.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmarks.chip import reference

COUNT_KEYS = ("jobs_finished", "preemptions", "nat_drops")
FLOAT_KEYS = ("cost", "accel_days", "busy_hours", "egress_usd",
              "stagein_hours", "cache_hit_fraction")
GAP_KEYS = ("cost", "accel_days", "jobs_finished", "preemptions",
            "nat_drops", "egress_usd")
ROW_KEYS = COUNT_KEYS + FLOAT_KEYS + ("by_provider", "events_fired")
FLOAT_RTOL = 1e-4
NUMBERS = ("lanes_off", "worst_gap", "mean_gap")
SAMPLE_STREAM = 0x5EED      # keeps the draw apart from the lane seeds


def slim(row: dict) -> dict:
    """The compared part of a sweep row, with its lane seed."""
    return {"seed": row["seed"], **{k: row[k] for k in ROW_KEYS}}


def draw(calls: Sequence[Tuple[List[dict], List[dict]]], n: int,
         seed: int) -> List[Tuple[dict, int, dict]]:
    """``n`` lanes, drawn from the seed, among the window's calls; each
    call is ``(spec_dicts, rows)`` with rows in ``api.sweep``'s order
    (spec-major)."""
    lanes = [(ci, li) for ci, (_specs, rows) in enumerate(calls)
             for li in range(len(rows))]
    if not lanes:
        return []
    rng = np.random.default_rng([int(seed), SAMPLE_STREAM])
    pick = rng.choice(len(lanes), size=min(n, len(lanes)), replace=False)
    out = []
    for j in sorted(pick):
        ci, li = lanes[j]
        specs, rows = calls[ci]
        seeds_per_spec = len(rows) // len(specs)
        row = rows[li]
        spec = specs[li // seeds_per_spec]
        out.append((spec, int(row["seed"]), row))
    return out


def _rel(a: float, b: float) -> float:
    return abs(float(a) - float(b)) / max(abs(float(b)), 1.0)


def row_differs(got: dict, want: dict) -> bool:
    if any(got[k] != want[k] for k in COUNT_KEYS):
        return True
    if got["by_provider"] != want["by_provider"]:
        return True
    if got["events_fired"] != want["events_fired"]:
        return True
    return any(abs(got[k] - want[k]) > FLOAT_RTOL * max(abs(want[k]), 1e-9)
               for k in FLOAT_KEYS)


def numbers(got: Sequence[dict], want: Sequence[dict]) -> Dict[str, float]:
    """The three compared numbers over paired rows."""
    if not got:
        return {"lanes_off": 1.0, "worst_gap": float("inf"),
                "mean_gap": float("inf")}
    off = sum(row_differs(g, w) for g, w in zip(got, want)) / len(got)
    worst = max(_rel(g[k], w[k]) for g, w in zip(got, want)
                for k in GAP_KEYS)
    mean = max(_rel(np.mean([g[k] for g in got]),
                    np.mean([w[k] for w in want])) for k in GAP_KEYS)
    return {"lanes_off": float(off), "worst_gap": float(worst),
            "mean_gap": float(mean)}


def judge(nums: Dict[str, float], limits: dict) -> Tuple[bool, dict]:
    """Each number beside its limit, and whether all are within."""
    checks = {k: {"value": nums[k], "limit": limits[k]["limit"]}
              for k in NUMBERS}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


def check(drawn: Sequence[Tuple[dict, int, dict]], tables: dict
          ) -> Tuple[Dict[str, float], List[dict]]:
    """The float32 reference over the drawn lanes; returns the numbers
    and the reference rows."""
    want = reference.reference_rows([(s, seed) for s, seed, _ in drawn],
                                    tables)
    return numbers([r for _s, _seed, r in drawn], want), want
