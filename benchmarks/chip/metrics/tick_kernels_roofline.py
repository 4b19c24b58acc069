"""The tick kernels' share of their bandwidth roofline: the bytes
their calls need at unpadded shapes (``kernels/<name>.py``), every tick
of every traced scan, over peak HBM bandwidth, divided by their summed
device time in the trace.  Absent when the trace names none of them."""
from benchmarks.chip import trace


def read(run):
    tr = run.trace
    if tr is None:
        return None
    scans = tr.span_count.get("sweep.scan", 0)
    dev_s = sum(s for name, s in tr.op_seconds.items() for k in run.kernels
                if trace.op_kind(name) in k.EVENT_NAMES)
    if dev_s <= 0.0 or scans == 0:
        return None
    need = sum(k.bytes_per_tick(run.shape) for k in run.kernels) \
        * run.shape["N"] * scans
    return 100.0 * need / run.peak["hbm_bytes_per_s"] / dev_s
