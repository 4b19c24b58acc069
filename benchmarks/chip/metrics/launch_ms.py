"""Scan dispatch: host milliseconds per call in the program's
``engine.launch`` span (the compiled scan called until it returns; a
recompile lands here), over the untraced window of a ``--trace 1``
run."""
from benchmarks.chip import progspans


def read(run):
    return progspans.span_ms(run, "engine.launch")
