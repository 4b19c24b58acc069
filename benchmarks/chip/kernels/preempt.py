"""The preemption fan-out kernel (``campaign_preempt`` in kernels/ops.py
over ``_alloc_body``): (lane, group) rows of W + 2 occupancy cells and
a scale per row in, the killed counts out; twice a tick (kill to
target, then the sampled preemptions)."""

EVENT_NAMES = ("campaign_preempt",)


def bytes_per_tick(s: dict) -> int:
    R, C = s["B"] * s["G"], s["W"] + 2
    return 2 * 4 * (2 * R * C + R)
