"""Wait for an interactive what-if: the 90th percentile of the wall
time of every call in the window."""
import numpy as np


def read(run):
    if not run.calls:
        return None
    return float(np.percentile([c["t1"] - c["t0"] for c in run.calls], 90))
