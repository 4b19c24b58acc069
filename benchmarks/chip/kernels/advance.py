"""The progress-sync kernel (``campaign_advance`` over
``_advance_body``): (lane, group) rows of W busy counts and their
finish mask in, the advanced counts and the finished count per row out;
once a tick."""

EVENT_NAMES = ("campaign_advance",)


def bytes_per_tick(s: dict) -> int:
    R, W = s["B"] * s["G"], s["W"]
    return 4 * (3 * R * W + R)
