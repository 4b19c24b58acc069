"""Host spans around the sweep's layers, recorded from the benchmark's
own files: the program's functions are wrapped at run time, in the
traced run only.  Each wrapper adds its host seconds to the current
call's totals and opens a ``jax.profiler.TraceAnnotation`` of the same
name, so host spans and device events share the trace's clock; with the
profiler off the annotation costs next to nothing, so the host seconds
are timed in the untraced window."""
from __future__ import annotations

import functools
import time
from collections import defaultdict
from typing import Dict, List

#: span name -> (module, attribute path) of the wrapped function
WRAPPED = {
    "sweep.prepare": ("repro.core.sweep_jax", "_prepare"),
    "sweep.bake": ("repro.core.sweep_jax", "JaxSweepEngine.__init__"),
    "sweep.scan": ("repro.core.sweep_jax", "JaxSweepEngine.run"),
    "sweep.results": ("repro.core.sweep_jax", "JaxSweepEngine.lane_results"),
    "sweep.events": ("repro.core.sweep_jax", "JaxSweepEngine.lane_events"),
}
CALL = "sweep.call"     # one whole ``api.sweep`` call, opened by the loop


class Spans:
    """Per-call host seconds by span name."""

    def __init__(self):
        self.calls: List[Dict[str, float]] = []
        self._cur: Dict[str, float] = defaultdict(float)
        self._undo = []

    def begin_call(self):
        self._cur = defaultdict(float)

    def end_call(self):
        self.calls.append(dict(self._cur))

    def _wrap(self, fn, name):
        import jax

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(name):
                    return fn(*args, **kwargs)
            finally:
                self._cur[name] += time.perf_counter() - t0
        return wrapper

    def install(self):
        import importlib
        for name, (mod, path) in WRAPPED.items():
            owner = importlib.import_module(mod)
            *outer, attr = path.split(".")
            for part in outer:
                owner = getattr(owner, part)
            fn = getattr(owner, attr)
            setattr(owner, attr, self._wrap(fn, name))
            self._undo.append((owner, attr, fn))

    def uninstall(self):
        while self._undo:
            owner, attr, fn = self._undo.pop()
            setattr(owner, attr, fn)

    def take(self) -> List[Dict[str, float]]:
        """The calls recorded so far; recording starts afresh."""
        calls, self.calls = self.calls, []
        return calls


def per_call_ms(calls: List[Dict[str, float]], *names: str) -> List[float]:
    """Each call's summed host milliseconds in the named spans."""
    return [1e3 * sum(c.get(n, 0.0) for n in names) for c in calls]
