"""The campaign front door: ``run(spec_or_specs, seeds=..., engine=...)``.

One entry point executes any declarative :class:`~repro.core.spec.
CampaignSpec` — solo or sweep — and returns typed results:

  * one spec, one seed  -> a solo simulation, returned as a
    :class:`~repro.core.spec.CampaignResult`,
  * one spec x many seeds, or many specs -> a (seed x spec) sweep on the
    batched lock-step engine, returned as a
    :class:`~repro.core.sweep.SweepResult`.

``engine`` selects the execution path:

  * ``"auto"`` (default): solo array engine for a single (spec, seed),
    the batched sweep engine otherwise,
  * ``"array"`` / ``"object"``: force solo engines (sweeps loop them
    sequentially — the reference semantics),
  * ``"batched"``: force the lock-step sweep engine,
  * ``"sequential"``: alias for a sequential solo-array loop,
  * ``"jax"``: the jit-compiled sweep engine (core/sweep_jax.py) —
    statistically equivalent, not bit-identical (see below).

Every batched lane is bit-reproducible against its solo run at the same
(spec, seed) — pinned by tests/test_sweep.py and tests/test_spec.py.
``engine="jax"`` sits in a separate **statistical-equivalence tier**:
it replaces per-instance PCG64 draws with per-group threefry Poisson
totals, so results match the bit-identical engines in distribution
(mean/p5/p95 bands on cost, GPU-days and jobs — pinned by
tests/test_sweep_jax.py via
``engine_equivalence.assert_statistically_equivalent``), never
byte-for-byte.  The allowed-engine sets below (:data:`SOLO_ENGINES`,
:data:`SWEEP_ENGINES`, :data:`ENGINES`) are the single source of truth
for ``run``/``sweep`` validation and the ``campaigns`` CLI choices.
"""
from __future__ import annotations

import numbers
from typing import Iterable, List, Sequence, Tuple, Union

import numpy as np

from repro.core.spec import (CampaignResult, CampaignSpec, check_collect,
                             paper_spec, run_solo)
from repro.core.sweep import SweepResult, run_batched_detailed

__all__ = ["run", "sweep", "paper_spec", "CampaignResult", "SweepResult",
           "SOLO_ENGINES", "SWEEP_ENGINES", "ENGINES", "TRACE_ENGINES"]

#: the allowed-engine sets — the one place the names live.  ``run``,
#: ``sweep`` and the ``campaigns`` CLI ``--engine`` choices all read
#: these; adding an engine here is the whole registration step.
SOLO_ENGINES = frozenset({"array", "object"})
SWEEP_ENGINES = SOLO_ENGINES | {"batched", "sequential", "jax"}
ENGINES = SWEEP_ENGINES | {"auto"}

#: engines with a per-instance trace surface (``collect="trace"``):
#: every bit-identical engine; the statistical jax tier is excluded
TRACE_ENGINES = frozenset(SWEEP_ENGINES - {"jax"})


def _no_trace_error() -> ValueError:
    """The one error both ``run`` and ``sweep`` raise for
    ``engine="jax", collect="trace"`` — it names the engines that DO
    have a trace surface so the fix is in the message."""
    return ValueError(
        'engine="jax" is statistical — it has no per-instance event '
        'stream to trace; use collect="summary", or pick a '
        "trace-capable engine: " + ", ".join(sorted(TRACE_ENGINES)))


def _check_engine(engine: str, allowed: frozenset, what: str) -> str:
    """The shared engine validation (both ``run`` layers used to raise
    their own, differently-worded errors)."""
    if engine not in allowed:
        raise ValueError(
            f"unknown {what} engine {engine!r}; choose one of "
            f"{', '.join(sorted(allowed))}")
    return engine


def _as_seed(s) -> int:
    """Seeds are exact campaign identities: a float like 3.7 used to
    truncate to 3 via ``int()`` and silently run a different campaign,
    and ``True`` (an ``Integral`` subclass; ``np.bool_`` registers with
    neither ABC) would silently run seed 1 — all are rejected outright."""
    if isinstance(s, (bool, np.bool_)):
        raise TypeError(
            f"seeds must be integers, got {s!r} (bool); a bool seed "
            f"would silently run seed {int(s)} — pass an int")
    if isinstance(s, numbers.Real) and not isinstance(s, numbers.Integral):
        raise TypeError(
            f"seeds must be integers, got {s!r} ({type(s).__name__}); "
            "float seeds would be silently truncated — pass an int")
    return int(s)


def sweep(specs: Sequence[CampaignSpec], seeds: Sequence[int],
          engine: str = "batched", collect: str = "summary") -> SweepResult:
    """Run every (spec x seed) lane and always return a SweepResult
    (``run()`` delegates here for multi-lane inputs).  ``engine``:
    "batched" (lock-step array program), "jax" (compiled scan —
    statistical tier, no trace surface) or "sequential" / "array" /
    "object" (solo reference loop).  ``collect="trace"`` additionally
    records one typed ``CampaignTrace`` per lane (``SweepResult.traces``
    / ``trace_for``)."""
    check_collect(collect)
    if collect == "stream":
        raise ValueError(
            'collect="stream" feeds ONE campaign through one sink — '
            'sweeps record per-lane traces with collect="trace" '
            "(SweepResult.traces) instead")
    _check_engine(engine, SWEEP_ENGINES, "sweep")
    specs = list(specs)
    if not specs:
        raise ValueError("sweep() needs at least one spec")
    seeds = [_as_seed(seed) for seed in seeds]
    if not seeds:
        raise ValueError("sweep() needs at least one seed")
    lanes = [(spec.to_spec(), seed) for spec in specs for seed in seeds]
    if engine == "batched":
        detailed = run_batched_detailed(lanes, collect=collect)
    elif engine == "jax":
        if collect == "trace":
            raise _no_trace_error()
        from repro.core.sweep_jax import run_jax_detailed
        detailed = run_jax_detailed(lanes)
    else:
        eng = engine if engine in SOLO_ENGINES else None
        detailed = []
        for spec, seed in lanes:
            res, ctl = run_solo(spec, seed, engine=eng, collect=collect)
            detailed.append((res.to_dict(), list(ctl.events_fired),
                             res.trace))
    rows = [{"scenario": spec.name, "seed": seed, **res,
             "events_fired": events}
            for (spec, seed), (res, events, _tr) in zip(lanes, detailed)]
    traces = [tr for _res, _ev, tr in detailed] \
        if collect == "trace" else None
    return SweepResult(rows, traces=traces)


def _coerce_specs(spec_or_specs) -> Tuple[List[CampaignSpec], bool]:
    if hasattr(spec_or_specs, "to_spec"):
        return [spec_or_specs.to_spec()], True
    specs = [s.to_spec() for s in spec_or_specs]
    if not specs:
        raise ValueError("run() needs at least one spec")
    return specs, False


def _coerce_seeds(seeds) -> Tuple[List[int], bool]:
    if isinstance(seeds, str):
        # a string is iterable per-character: "2021" would silently
        # become the 4-seed sweep [2, 0, 2, 1] — treat it as one seed
        return [int(seeds)], True
    if not isinstance(seeds, Iterable):
        return [_as_seed(seeds)], True
    seeds = [_as_seed(s) for s in seeds]
    if not seeds:
        raise ValueError("run() needs at least one seed")
    return seeds, False


def run(spec_or_specs: Union[CampaignSpec, Sequence[CampaignSpec]],
        seeds: Union[int, Sequence[int]] = 2021,
        engine: str = "auto",
        collect: str = "summary",
        sink=None) -> Union[CampaignResult, SweepResult]:
    """Execute campaign spec(s); see module docstring for dispatch.

    ``collect`` selects the results surface: ``"summary"`` (default —
    end-of-run totals only, the historical behavior), ``"trace"``,
    which additionally records the typed event stream (every launch /
    stop / preemption / pilot / NAT drop / job completion / timeline
    firing) as a :class:`~repro.core.events.CampaignTrace` on
    ``CampaignResult.trace`` (solo) or ``SweepResult.traces`` (sweeps),
    or ``"stream"``, which feeds that same canonical event stream
    through ``sink`` (a :class:`~repro.core.traceops.TraceSink` — JSONL
    /gzip file or callback) in bounded tick-windows so the full event
    list never exists in memory; the streamed bytes are identical to
    ``collect="trace"`` serialization.  ``"stream"`` is one campaign
    into one sink: solo-shaped input only.  Collection is RNG-free:
    summary numbers are identical either way, and all trace-capable
    engines emit byte-identical serialized traces."""
    check_collect(collect)
    specs, single_spec = _coerce_specs(spec_or_specs)
    seed_list, single_seed = _coerce_seeds(seeds)
    solo = single_spec and len(specs) == 1 and len(seed_list) == 1
    _check_engine(engine, ENGINES, "run")
    if collect == "stream":
        if not solo:
            raise ValueError(
                'collect="stream" feeds ONE campaign through one sink; '
                "pass one spec and one seed (for sweeps, use "
                'collect="trace" and SweepResult.traces)')
        if sink is None:
            raise ValueError(
                'collect="stream" needs a sink= (e.g. '
                "repro.core.traceops.JsonlStreamSink)")
    elif sink is not None:
        raise ValueError('sink= is only meaningful with collect="stream"')

    if solo and engine == "batched":     # forced single-lane batched run
        (res, events, trace), = run_batched_detailed(
            [(specs[0], seed_list[0])], collect=collect,
            sinks=None if sink is None else [sink])
        return CampaignResult.from_results(
            res, spec=specs[0], seed=seed_list[0], engine="batched",
            events_fired=tuple(events), trace=trace)
    if solo and engine == "jax":         # forced single-lane compiled run
        if collect in ("trace", "stream"):
            raise _no_trace_error()
        from repro.core.sweep_jax import run_jax_detailed
        (res, events, trace), = run_jax_detailed(
            [(specs[0], seed_list[0])])
        return CampaignResult.from_results(
            res, spec=specs[0], seed=seed_list[0], engine="jax",
            events_fired=tuple(events), trace=trace)
    if solo:
        eng = None if engine in ("auto", "sequential") else engine
        result, _ctl = run_solo(specs[0], seed_list[0], engine=eng,
                                collect=collect, sink=sink)
        return result

    return sweep(specs, seed_list,
                 engine="batched" if engine == "auto" else engine,
                 collect=collect)
