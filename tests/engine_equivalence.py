"""Reusable differential harness for the repo's flagship invariant:
every engine interprets a CampaignSpec bit-identically —

    solo object == solo array == batched sweep lane

``assert_results_match`` is the single comparison policy (counts exact,
rounded $ values one rounding ulp of slack) that used to be duplicated
across test_spec.py / test_sweep.py / test_fleet_engine.py.
``assert_engines_equivalent`` runs one (spec, seed) campaign on the solo
array reference plus any requested engines and cross-checks results AND
``events_fired`` provenance; ``assert_sweep_equivalent`` does the same
for a whole (specs x seeds) sweep against the sequential reference loop.

``serialized_trace`` / ``assert_traces_equivalent`` extend the contract
to the typed event-trace API (core/events.py): at matching (spec, seed)
every engine must emit a **byte-identical** serialized CampaignTrace.

``assert_statistically_equivalent`` is the *statistical* tier for
``engine="jax"`` (core/sweep_jax.py): the compiled engine replaces
per-instance PCG64 draws with per-group threefry Poisson totals, so it
can never be bit-identical — instead its per-scenario means must sit
within a relative band of the batched reference and its [p5, p95]
spread must lie inside the reference band widened by the same margin,
for cost, GPU-days and jobs over a seed sweep.

Where hypothesis is installed, this module also exports the strategies
(``spec_strategy`` / ``event_strategy``) that generate random
CampaignSpec timelines — including the PriceCurve / GpuSlicing surfaces
— for the property tests in test_spec_properties.py.
"""
import numpy as np
import pytest

from repro.core.api import run, sweep as api_sweep
from repro.core.spec import run_solo
from repro.core.sweep_jax import STAT_BANDS, band_violations


def assert_results_match(lane, solo):
    """Counts exact; rounded $ values get one rounding ulp of slack."""
    assert set(lane) >= set(solo)
    for k in solo:
        vs, vl = solo[k], lane[k]
        if isinstance(vs, dict):
            assert set(vs) == set(vl), k
            for kk in vs:
                assert vl[kk] == pytest.approx(vs[kk], rel=1e-9,
                                               abs=0.02), (k, kk)
        elif isinstance(vs, (int, np.integer)) and not isinstance(vs, bool):
            assert vl == vs, k
        else:
            assert vl == pytest.approx(vs, rel=1e-9, abs=0.02), k


def assert_engines_equivalent(spec, seed, engines=("batched",),
                              check_events=True):
    """Run one (spec, seed) campaign on the solo array engine (the
    reference semantics) and on every engine in ``engines`` ("batched"
    and/or "object"), asserting bit-identical results and — for engines
    that carry it — identical executed-event provenance.  Returns the
    reference CampaignResult."""
    ref, _ctl = run_solo(spec, seed)
    ref_d = ref.to_dict()
    for engine in engines:
        if engine == "object":
            other, _ = run_solo(spec, seed, engine="object")
        elif engine == "batched":
            other = run(spec, seeds=seed, engine="batched")
        else:
            raise ValueError(f"unknown differential engine {engine!r}")
        assert_results_match(other.to_dict(), ref_d)
        if check_events:
            assert list(other.events_fired) == list(ref.events_fired), \
                engine
    return ref


def assert_sweep_equivalent(specs, seeds):
    """Batched (specs x seeds) sweep row-for-row against the sequential
    solo reference loop, events_fired included.  Returns the batched
    SweepResult."""
    batched = api_sweep(specs, seeds, engine="batched")
    seq = api_sweep(specs, seeds, engine="sequential")
    assert len(batched.rows) == len(specs) * len(seeds)
    for rb, rs in zip(batched.rows, seq.rows):
        assert (rb["scenario"], rb["seed"]) == (rs["scenario"], rs["seed"])
        assert_results_match(rb, rs)
        assert rb["events_fired"] == rs["events_fired"]
    return batched


def assert_statistically_equivalent(specs, seeds, engine="jax",
                                    bands=None, reference="batched"):
    """Run a (specs x seeds) sweep on the statistical ``engine`` and on
    the bit-identical ``reference``, asserting for every scenario and
    every metric in ``bands`` (default :data:`STAT_BANDS`) that

      * the means agree within ``rel * |reference mean|``, and
      * the engine's [p5, p95] seed spread lies inside the reference's
        band widened by the same margin (shape, not just location).

    Returns ``(engine SweepResult, reference SweepResult)``."""
    bands = dict(STAT_BANDS if bands is None else bands)
    metrics = tuple(bands)
    got = api_sweep(specs, seeds, engine=engine)
    ref = api_sweep(specs, seeds, engine=reference)
    gs, rs = got.summary(metrics), ref.summary(metrics)
    assert set(gs) == set(rs)
    assert band_violations(rs, gs, bands) == []
    return got, ref


def serialized_trace(spec, seed, engine: str = "array") -> str:
    """One (spec, seed) campaign's canonical JSONL trace bytes on the
    requested engine ("array" | "object" | "batched")."""
    if engine == "batched":
        res = run(spec, seeds=seed, engine="batched", collect="trace")
    elif engine in ("array", "object"):
        res, _ctl = run_solo(spec, seed,
                             engine=None if engine == "array" else engine,
                             collect="trace")
    else:
        raise ValueError(f"unknown trace engine {engine!r}")
    return res.trace.to_jsonl()


def assert_traces_equivalent(spec, seed, engines=("batched",)) -> str:
    """The trace contract: every engine in ``engines`` serializes the
    same (spec, seed) campaign to exactly the solo-array reference
    bytes.  Returns the reference JSONL."""
    ref = serialized_trace(spec, seed)
    for engine in engines:
        assert serialized_trace(spec, seed, engine) == ref, engine
    return ref


def assert_stream_equivalent(spec, seed, tmp_dir,
                             engines=("array", "object", "batched"),
                             ref: str = None) -> str:
    """The streaming contract: ``collect="stream"`` through a gzip
    :class:`~repro.core.traceops.JsonlStreamSink`, re-read from disk,
    equals the ``collect="trace"`` serialized bytes on every engine in
    ``engines``.  ``tmp_dir`` is a writable directory (pytest's
    ``tmp_path``); pass ``ref`` to reuse already-computed reference
    JSONL.  Returns the reference JSONL."""
    import gzip
    import os
    from repro.core.traceops import JsonlStreamSink
    if ref is None:
        ref = serialized_trace(spec, seed)
    ref_bytes = ref.encode("utf-8")
    for engine in engines:
        path = os.path.join(str(tmp_dir), f"stream-{engine}.jsonl.gz")
        sink = JsonlStreamSink(path)
        res = run(spec, seeds=seed, engine=engine, collect="stream",
                  sink=sink)
        assert res.trace is None, engine       # streamed, not held
        assert sink.closed and not os.path.exists(path + ".spool")
        with gzip.open(path, "rb") as f:
            assert f.read() == ref_bytes, engine
    return ref


# -- hypothesis strategies (exported only where hypothesis exists) ---------

try:
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                  # pragma: no cover
    st = None
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    from repro.core.dataplane import DataOrigin, DataPlane
    from repro.core.spec import CampaignSpec, GpuSlicing
    from repro.core.timeline import event_strategies

    def event_strategy():
        """One random timeline event — every registered kind included,
        derived from the registry so newly registered events are swept
        here with zero hand edits."""
        return st.one_of(*event_strategies(st))

    def dataplane_strategy():
        """A random DataPlane over the t4 catalog's base providers —
        origins with and without caches or egress pricing."""
        origin = st.builds(
            DataOrigin,
            bandwidth_gbps=st.sampled_from([0.5, 2.0, 8.0]),
            egress_usd_per_gb=st.sampled_from([0.0, 0.05, 0.12]),
            cache_hit_rate=st.sampled_from([0.0, 0.5, 0.9]),
            cache_bandwidth_gbps=st.sampled_from([0.0, 16.0]))
        return st.dictionaries(
            st.sampled_from(["azure", "gcp", "aws"]), origin,
            min_size=1, max_size=3).map(DataPlane)

    def spec_strategy():
        """A random small CampaignSpec over every spec surface, the new
        PriceCurve timeline events, GpuSlicing and DataPlane fields
        included."""
        return st.builds(
            CampaignSpec,
            name=st.sampled_from(["a", "b"]),
            catalog=st.sampled_from(["t4", "heterogeneous"]),
            capacity_scale=st.sampled_from([0.5, 1.0]),
            spot=st.booleans(),
            ondemand_fraction=st.sampled_from([0.0, 0.25]),
            price_scale=st.sampled_from([0.8, 1.0, 1.25]),
            budget=st.sampled_from([2000.0, 8000.0, 1e9]),
            budget_floor_fraction=st.sampled_from([0.1, 0.2, 0.25]),
            downscale_target=st.integers(0, 300),
            duration_h=st.sampled_from([12.0, 24.0, 30.0]),
            lease_interval_s=st.sampled_from([120.0, 300.0]),
            job_wall_h=st.sampled_from([1.0, 4.0]),
            min_queue=st.sampled_from([500, 4000]),
            gpu_slicing=st.one_of(
                st.none(),
                st.builds(GpuSlicing,
                          slices=st.sampled_from([2, 4, 7]),
                          price_factor=st.sampled_from([1.0, 1.1]),
                          tflops_factor=st.sampled_from([0.9, 1.0]))),
            job_input_gb=st.sampled_from([0.0, 2.0, 25.0]),
            dataplane=st.one_of(st.none(), dataplane_strategy()),
            timeline=st.lists(event_strategy(), max_size=5).map(tuple))
