"""The one traffic generator: a mix file's spec variants and the seed
schedule drawn from ``--seed``.

A mix (``traffic/<name>.json``) holds:

* ``grid``: spec fields and the values each takes; every combination,
  in file order (the last field varying fastest), is one spec variant
  of the configuration's spec (an empty grid: the spec alone);
* ``seeds_per_call``: lane seeds per sweep call, shared by every variant,
  so a call runs ``variants x seeds_per_call`` lanes;
* ``loop``: ``closed`` (one caller; the next call starts when the last
  returns);
* ``check_lanes``: lanes drawn from the window's calls for the
  comparison with the reference;
* ``trace_calls``: whole calls in the traced window of a ``--trace 1``
  run.

Every call gets lane seeds of its own; the same ``--seed`` gives the
same schedule.  This copies the ``planning_grid()`` axes idea of
``core/scenarios.py`` so that a change there does not move the
yardstick.
"""
from __future__ import annotations

import copy
import itertools
from typing import List

import numpy as np

SEED_SPACE = 2 ** 31


def variants(config: dict, mix: dict) -> List[dict]:
    """The spec dicts one call sweeps, each named after its grid point."""
    base = config["spec"]
    grid = mix.get("grid", {})
    keys = list(grid)
    out = []
    for i, values in enumerate(itertools.product(*(grid[k] for k in keys))):
        spec = copy.deepcopy(base)
        spec.update(zip(keys, values))
        if keys:
            spec["name"] = f"{base['name']}-v{i:03d}"
        out.append(spec)
    return out


class SeedSchedule:
    """Distinct lane seeds for each call, drawn from the run's seed."""

    def __init__(self, seed: int, per_call: int):
        self.rng = np.random.default_rng(int(seed))
        self.per_call = int(per_call)

    def next_call(self) -> List[int]:
        return [int(s) for s in self.rng.choice(
            SEED_SPACE, size=self.per_call, replace=False)]


def lanes_per_call(config: dict, mix: dict) -> int:
    return len(variants(config, mix)) * int(mix["seeds_per_call"])
