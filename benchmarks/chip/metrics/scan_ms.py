"""The scan as the host sees it: host milliseconds per call in the
``sweep.scan`` span (dispatch, device run, readback to host arrays),
timed over the untraced window of a ``--trace 1`` run."""
import numpy as np

from benchmarks.chip import spans


def read(run):
    if not run.span_calls:
        return None
    return float(np.mean(spans.per_call_ms(
        run.span_calls, "sweep.scan")))
