"""The billing kernel (``campaign_bill`` over ``_bill_body``): live
counts and rates per (lane, group) and the (G, P) provider map in,
spend per lane and per provider out; once a tick."""

EVENT_NAMES = ("campaign_bill",)


def bytes_per_tick(s: dict) -> int:
    B, G, P = s["B"], s["G"], s["P"]
    return 4 * (2 * B * G + G * P + B + B * P)
