"""Scan staging: host milliseconds per call in the program's
``engine.put`` span (the scan's arguments put on the device), over the
untraced window of a ``--trace 1`` run."""
from benchmarks.chip import progspans


def read(run):
    return progspans.span_ms(run, "engine.put")
