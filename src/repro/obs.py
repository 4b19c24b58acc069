"""Host spans and counters of the program's own work, kept in-process.

A sweep opens one call record (:func:`call`); inside it :func:`span`
times a step of the work and :func:`count` adds to a counter of the
innermost open span.  Recording is always on.  Each span also opens a
``jax.profiler.TraceAnnotation`` of its name, so that in a profile the
host spans share the device trace's clock; with no profiler running the
annotation costs next to nothing.

Read back with :func:`calls` (the newest :data:`KEEP` call records) and
:func:`totals` (cumulative seconds per span name and cumulative
counters, for an operator to scrape)::

    from repro import obs
    last = obs.calls()[-1]
    last.seconds("engine.put"), last.counters.get("h2d_bytes")

The first :func:`call` installs two hooks, once per process: a
``gc.callbacks`` hook that adds each collection's pause to the counters
``gc_s`` and ``gc_collections``, and a ``jax.monitoring`` listener that
adds backend compiles and compile-cache loads to ``compiles`` and
``compile_s``.  Both land on the span open when they happen (outside any
call, in :func:`totals` only).

Spans and the open call are per thread; the kept records and the totals
are shared by the process.
"""
from __future__ import annotations

import contextlib
import gc
import itertools
import threading
import time
from collections import defaultdict, deque
from typing import Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "Call", "call", "span", "count", "calls", "totals",
           "KEEP", "COMPILE_EVENTS"]

#: call records kept, newest last: a 51 s window of 32-lane sweeps is
#: ~400 calls
KEEP = 4096

#: ``jax.monitoring`` duration events counted as compiles
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class Span:
    """One timed step: its name, the name of the span (or call) it ran
    inside, its ``time.perf_counter`` interval and its counters."""

    __slots__ = ("name", "parent", "start", "end", "counters")

    def __init__(self, name: str, parent: Optional[str], start: float):
        self.name, self.parent = name, parent
        self.start, self.end = start, start
        self.counters: Dict[str, float] = {}

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def __repr__(self):
        return (f"Span({self.name!r}, parent={self.parent!r}, "
                f"{self.seconds * 1e3:.3f} ms, {self.counters})")


class Call:
    """One call record: an id, its interval, its spans in the order they
    opened, and the sum of every counter counted while it was open."""

    __slots__ = ("id", "name", "t0", "t1", "spans", "counters")

    def __init__(self, id_: int, name: str, t0: float):
        self.id, self.name = id_, name
        self.t0 = self.t1 = t0
        self.spans: List[Span] = []
        self.counters: Dict[str, float] = {}

    @property
    def seconds_total(self) -> float:
        return self.t1 - self.t0

    def seconds(self, *names: str) -> float:
        """Summed seconds of the spans with these names."""
        return sum(s.seconds for s in self.spans if s.name in names)

    def self_seconds(self) -> Dict[str, float]:
        """Seconds per span name (and the call's own name) spent outside
        the spans opened inside it."""
        own = {self.name: self.seconds_total}
        for s in self.spans:
            own[s.name] = own.get(s.name, 0.0) + s.seconds
        for s in self.spans:
            own[s.parent] -= s.seconds
        return own

    def __repr__(self):
        return (f"Call({self.id}, {self.name!r}, "
                f"{self.seconds_total * 1e3:.3f} ms, "
                f"{len(self.spans)} spans, {self.counters})")


_local = threading.local()          # .stack: the open call, then spans
_lock = threading.RLock()           # a gc hook may fire while it is held
_kept: deque = deque(maxlen=KEEP)
_ids = itertools.count(1)
_seconds: Dict[str, float] = defaultdict(float)
_entries: Dict[str, int] = defaultdict(int)
_counters: Dict[str, float] = defaultdict(float)
_hooked = False
_gc_start: Dict[int, float] = {}


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def count(name: str, n: float = 1) -> None:
    """Adds ``n`` to the counter ``name`` of the innermost open span and
    of the open call, and to the process's totals."""
    stack = _stack()
    if stack:
        top = stack[-1]
        top.counters[name] = top.counters.get(name, 0) + n
        if top is not stack[0] and isinstance(stack[0], Call):
            c = stack[0].counters
            c[name] = c.get(name, 0) + n
    with _lock:
        _counters[name] += n


def _close(name: str, seconds: float):
    with _lock:
        _seconds[name] += seconds
        _entries[name] += 1


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Times the block as a span of the open call (with no call open,
    in :func:`totals` only)."""
    stack = _stack()
    s = Span(name, stack[-1].name if stack else None, time.perf_counter())
    if stack and isinstance(stack[0], Call):
        stack[0].spans.append(s)
    stack.append(s)
    try:
        with TraceAnnotation(name):
            yield
    finally:
        s.end = time.perf_counter()
        stack.pop()
        _close(name, s.seconds)


@contextlib.contextmanager
def call(name: str) -> Iterator[None]:
    """Opens one call record, kept when the block ends (raised or not).
    Inside an open call or span it is a span itself."""
    stack = _stack()
    if stack:
        with span(name):
            yield
        return
    _install_hooks()
    c = Call(next(_ids), name, time.perf_counter())
    stack.append(c)
    try:
        with TraceAnnotation(name):
            yield
    finally:
        c.t1 = time.perf_counter()
        stack.pop()
        _kept.append(c)
        _close(name, c.seconds_total)


def calls() -> List[Call]:
    """The kept call records, oldest first."""
    return list(_kept)


def totals() -> dict:
    """Cumulative ``seconds`` and ``entries`` per span and call name, and
    cumulative ``counters``, since the process started."""
    with _lock:
        return {"seconds": dict(_seconds), "entries": dict(_entries),
                "counters": dict(_counters)}


def _on_gc(phase: str, info: dict):
    gen = info.get("generation", -1)
    if phase == "start":
        _gc_start[gen] = time.perf_counter()
    elif gen in _gc_start:
        count("gc_s", time.perf_counter() - _gc_start.pop(gen))
        count("gc_collections")


def _on_jax_event(event: str, seconds: float, **_kw):
    if event in COMPILE_EVENTS:
        count("compiles")
        count("compile_s", seconds)


def _install_hooks():
    global _hooked
    with _lock:
        if _hooked:
            return
        from jax import monitoring
        gc.callbacks.append(_on_gc)
        monitoring.register_event_duration_secs_listener(_on_jax_event)
        _hooked = True
