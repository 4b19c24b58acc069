"""Scan readback: host milliseconds per call in the program's
``engine.fetch`` span (the scan's outputs copied to host arrays), over
the untraced window of a ``--trace 1`` run."""
from benchmarks.chip import progspans


def read(run):
    return progspans.span_ms(run, "engine.fetch")
