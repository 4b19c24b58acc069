"""Sweep throughput: lanes of the calls completed in the window, over
the time from the window's start to the end of the last completed
call (one closed-loop caller)."""


def read(run):
    if not run.calls:
        return None
    lanes = sum(c["lanes"] for c in run.calls)
    return lanes / (run.calls[-1]["t1"] - run.window_start)
