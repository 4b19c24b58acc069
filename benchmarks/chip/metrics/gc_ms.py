"""Garbage-collection pauses: host milliseconds per call that the
program's ``gc.callbacks`` hook counted (``gc_s``) while a sweep call
was open, over the untraced window of a ``--trace 1`` run."""
from benchmarks.chip import progspans


def read(run):
    s = progspans.counter_per_call(run, "gc_s")
    return None if s is None else 1e3 * s
