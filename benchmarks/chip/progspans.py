"""The program's own span and counter records (``repro.obs``), for the
calls of a traced run's untraced window.

The program opens one record per ``api.sweep`` call on the compiled
engine (``engine.call``), with host spans (``engine.put``,
``engine.launch``, ``engine.fetch``, ...) and counters (``gc_s``,
``h2d_bytes``, ...) inside it.  A record counts when it lies inside the
window: it starts at or after the window's start and ends by the end of
the window's last call, which leaves out the warm-up call before it and
the traced calls after it.  A program without the recorder keeps no
records, and every reader here then returns None.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

CALL = "engine.call"


def records() -> list:
    """Every sweep call record the program kept; none where it keeps
    none."""
    try:
        from repro import obs
    except ImportError:
        return []
    return [c for c in obs.calls() if c.name == CALL]


def in_window(run) -> list:
    """The records inside the run's untraced window."""
    if not run.calls:
        return []
    lo, hi = run.window_start, run.calls[-1]["t1"]
    return [c for c in records() if c.t0 >= lo and c.t1 <= hi]


def span_ms(run, *names: str) -> Optional[float]:
    """Mean host milliseconds per call in the named spans; None where no
    record lies in the window."""
    got = in_window(run)
    if not got:
        return None
    return float(np.mean([1e3 * c.seconds(*names) for c in got]))


def counter_per_call(run, name: str) -> Optional[float]:
    """Mean of a counter per call (0 for a call that never counted it);
    None where no record lies in the window."""
    got = in_window(run)
    if not got:
        return None
    return float(np.mean([c.counters.get(name, 0.0) for c in got]))
