"""The queue->pilot matcher kernel (``campaign_match``, the same
allocator body as preempt): lane rows of G idle counts and a scale per
row in, the taken counts out; once a tick."""

EVENT_NAMES = ("campaign_match",)


def bytes_per_tick(s: dict) -> int:
    B, G = s["B"], s["G"]
    return 4 * (2 * B * G + B)
