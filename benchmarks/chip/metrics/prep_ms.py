"""Lane prep and plane bake: host milliseconds per call in the
``sweep.prepare`` and ``sweep.bake`` spans,
timed over the untraced window of a ``--trace 1`` run."""
import numpy as np

from benchmarks.chip import spans


def read(run):
    if not run.span_calls:
        return None
    return float(np.mean(spans.per_call_ms(
        run.span_calls, "sweep.prepare", "sweep.bake")))
