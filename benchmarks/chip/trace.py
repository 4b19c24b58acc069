"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle
time, per-op device time, device time inside host spans, and the
longest idle gaps labelled by the host span open during them.

Device events are those on the ``XLA Ops`` line of each TPU core plane
(``/device:TPU:<n>``), named there by their HLO text; the reduction
keeps the instruction name (``campaign_preempt.17``), and
:func:`op_kind` drops its number (``campaign_preempt``), which is how a
kernel's events are found.  The scan's ``while`` op spans all of its
body's ops.  Host spans are ``TraceAnnotation`` events on the host
plane.  Both carry the host clock in nanoseconds.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
TOP = 10


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def op_name(hlo_text: str) -> str:
    """``%name.N = shape op(...)`` -> ``name.N``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """``campaign_preempt.17`` -> ``campaign_preempt``."""
    return re.sub(r"\.\d+$", "", name)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted intervals."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    got = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            got += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return got


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The idle intervals of a merged busy list inside the window."""
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


@dataclass
class Events:
    """What the reduction needs from one trace."""
    device: List[List[Tuple[str, float, float]]] = field(default_factory=list)
    host: List[Tuple[str, float, float]] = field(default_factory=list)


def read_xplane(path: str, span_names: Sequence[str]) -> Events:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ev = Events()
    wanted = set(span_names)
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops += [(op_name(e.name), e.start_ns, e.end_ns)
                            for e in line.events]
            ev.device.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ev.host += [(e.name, e.start_ns, e.end_ns)
                            for e in line.events if e.name in wanted]
    return ev


@dataclass
class Reduction:
    window_s: float
    busy_s: float                       # mean over devices
    op_seconds: Dict[str, float]        # summed device time per op name
    op_count: Dict[str, int]            # device events per op name
    in_span_busy_s: Dict[str, float]    # device busy inside each span name
    span_count: Dict[str, int]
    idle_gaps: List[Tuple[str, float]]  # longest first, by host span

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:TOP]]}


def reduce(ev: Events, window_span: str, label_spans: Sequence[str]
           ) -> Reduction:
    """Busy and idle over the window from the first ``window_span`` to
    the end of the last; each of the longest gaps is labelled by the
    span name that covers most of it (``host`` when none does)."""
    calls = sorted((s, e) for n, s, e in ev.host if n == window_span)
    if not calls:
        raise ValueError(f"trace holds no {window_span!r} span")
    if not any(ev.device):
        raise ValueError("trace holds no device op")
    window = (calls[0][0], calls[-1][1])
    spans: Dict[str, List[Interval]] = {}
    for n, s, e in ev.host:
        spans.setdefault(n, []).append((s, e))
    busy_each, op_ns, op_n = [], {}, {}
    for ops in ev.device:
        inside = [(n, s, e) for n, s, e in ops
                  if e > window[0] and s < window[1]]
        for n, s, e in inside:
            op_ns[n] = op_ns.get(n, 0.0) + (e - s)
            op_n[n] = op_n.get(n, 0) + 1
        busy_each.append(clip(union((s, e) for _n, s, e in inside), window))
    busy0 = busy_each[0]
    in_span = {n: overlap(busy0, union(iv)) * 1e-9 for n, iv in spans.items()}
    merged = {n: union(spans.get(n, [])) for n in label_spans}
    longest = sorted(gaps(busy0, window), key=lambda g: g[0] - g[1])[:TOP]
    idle = []
    for gap in longest:
        cover = {n: overlap([gap], iv) for n, iv in merged.items()}
        best = max(cover, key=cover.get) if cover else None
        idle.append((best if best and cover[best] > 0 else "host",
                     (gap[1] - gap[0]) * 1e-9))
    return Reduction(
        window_s=(window[1] - window[0]) * 1e-9,
        busy_s=sum(total(b) for b in busy_each) / len(busy_each) * 1e-9,
        op_seconds={n: v * 1e-9 for n, v in op_ns.items()},
        op_count=op_n,
        in_span_busy_s=in_span,
        span_count={n: len(iv) for n, iv in spans.items()},
        idle_gaps=idle)
