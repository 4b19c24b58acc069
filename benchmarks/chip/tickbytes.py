"""Bytes one tick of the sweep needs, from the logical shapes alone.

``shape`` holds B lanes, G groups, W progress steps (with the staging
extension where the data plane stages), L checkpoint levels, P
providers, N ticks, and the variant flags ``dp_gating``,
``dp_staging`` and ``nat_any`` (some group's NAT drops its pilots).  A
tick reads and writes its carry once and reads its control planes once;
the uniforms are drawn in registers.  These are the bytes whatever
implements the tick: no padding, no one-hot expansion.
The tick is bandwidth-bound: it does a few integer or float operations
per byte, far below the chip's ratio of peak operations to bandwidth.
"""
from __future__ import annotations

I4 = 4          # int32 / float32
B1 = 1          # bool


def carry_bytes(s: dict) -> int:
    B, G, W, L, P = s["B"], s["G"], s["W"], s["L"], s["P"]
    n = I4 * (3 * B * G            # idle, pilot-dead, per-group target
              + B * G * W          # busy by progress step
              + B * L              # requeued jobs by checkpoint level
              + 9 * B              # fresh queue, spent, infra, cap tick,
              #                      preemptions, NAT drops, finished,
              #                      GPU hours, busy hours
              + 2 * B * P)         # spend and busy hours by provider
    n += B1 * (5 * B + 2 * B)      # alert thresholds fired, capped, pending
    if s["dp_staging"]:
        n += I4 * (3 * B * G + 3 * B)   # hit credit, virgins, egress;
        #                                 hits, misses, staging time
    return n


def plane_bytes(s: dict) -> int:
    """Control planes and per-lane constants one tick reads."""
    B, G = s["B"], s["G"]
    n = I4 * 2 * B * G              # rate, capacity
    n += B1 * B + I4 * 5 * B        # outage; floor, downscale, queue
    #                                 level, uncapped and capped target
    n += I4 * 3 * B                 # budget, overhead, finish step
    if s["nat_any"]:
        n += I4 * B * G             # NAT-disconnected groups
    if s["dp_gating"]:
        n += B1 * B * G             # origin up
    if s["dp_staging"]:
        n += I4 * B * G + B1 * B * G    # miss stage ticks, flush edge
    return n


def tick_bytes(s: dict) -> int:
    return 2 * carry_bytes(s) + plane_bytes(s)


def call_bytes(s: dict) -> int:
    """Every tick of one scan."""
    return s["N"] * tick_bytes(s)
