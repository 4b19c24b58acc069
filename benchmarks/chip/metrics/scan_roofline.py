"""The scan's share of its bandwidth roofline: the bytes every tick
needs (``tickbytes.call_bytes``, from the logical shapes) over peak HBM
bandwidth, divided by the device busy time inside the ``sweep.scan``
spans.  Bytes bound it: the tick does a few operations per byte."""
from benchmarks.chip import tickbytes


def read(run):
    tr = run.trace
    if tr is None:
        return None
    busy = tr.in_span_busy_s.get("sweep.scan", 0.0)
    scans = tr.span_count.get("sweep.scan", 0)
    if busy <= 0.0 or scans == 0:
        return None
    need_s = scans * tickbytes.call_bytes(run.shape) \
        / run.peak["hbm_bytes_per_s"]
    return 100.0 * need_s / busy
