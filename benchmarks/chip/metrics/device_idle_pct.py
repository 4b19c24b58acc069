"""The device's idle share over the traced window of whole calls:
1 - (union of device op intervals) / window."""


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0.0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
