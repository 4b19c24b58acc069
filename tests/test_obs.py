"""The program's span-and-counter recorder (``repro.obs``) and the spans
the compiled sweep opens with it: nesting and self time, the bounded
ring, counters on the open span, the gc and compile hooks, how much of
a sweep call the spans cover, results unchanged by them, and the tick
scopes in the lowered scan's debug info."""
import contextlib
import gc
import json
import re
import time

import jax
import pytest

from repro import obs
from repro.core import sweep_jax
from repro.core.spec import CampaignSpec
from repro.core.sweep import _prepare

SEEDS = (2021, 7)
SCAN_STEPS = ("engine.put", "engine.launch", "engine.wait", "engine.fetch")
CALL_STEPS = ("engine.prepare", "engine.bake", "engine.scan",
              "engine.results", "engine.events")


def _last():
    return obs.calls()[-1]


def test_spans_nest_with_parents_ids_and_self_time():
    with obs.call("t.first"):
        pass
    with obs.call("t.call"):
        with obs.span("t.outer"):
            with obs.span("t.inner"):
                time.sleep(0.01)
            with obs.call("t.nested"):          # a call inside a call
                pass
            time.sleep(0.005)
    c = _last()
    first = obs.calls()[-2]
    assert (first.name, c.name) == ("t.first", "t.call")
    assert c.id == first.id + 1
    assert [(s.name, s.parent) for s in c.spans] == [
        ("t.outer", "t.call"), ("t.inner", "t.outer"),
        ("t.nested", "t.outer")]
    outer, inner, nested = c.spans
    assert c.t0 <= outer.start <= inner.start < inner.end \
        <= nested.start <= nested.end <= outer.end <= c.t1
    own = c.self_seconds()
    assert own["t.inner"] == pytest.approx(inner.seconds)
    assert inner.seconds >= 0.01
    assert own["t.outer"] == pytest.approx(
        outer.seconds - inner.seconds - nested.seconds)
    assert own["t.outer"] >= 0.005
    assert own["t.call"] == pytest.approx(c.seconds_total - outer.seconds)
    assert sum(own.values()) == pytest.approx(c.seconds_total)
    assert c.seconds("t.inner", "t.nested") == pytest.approx(
        inner.seconds + nested.seconds)


def test_a_raising_block_is_still_recorded():
    before = obs.totals()["entries"].get("t.raise", 0)
    with pytest.raises(RuntimeError):
        with obs.call("t.raise.call"):
            with obs.span("t.raise"):
                raise RuntimeError("boom")
    c = _last()
    assert c.name == "t.raise.call" and c.spans[0].end >= c.spans[0].start
    assert obs.totals()["entries"]["t.raise"] == before + 1


def test_spans_outside_a_call_go_to_the_totals_only():
    kept = obs.calls()[-1:]
    before = obs.totals()["entries"].get("t.bare.inner", 0)
    with obs.span("t.bare"):
        with obs.span("t.bare.inner"):
            obs.count("t.bare.n")
    assert obs.calls()[-1:] == kept
    assert obs.totals()["entries"]["t.bare.inner"] == before + 1


def test_ring_keeps_the_newest_records():
    for _ in range(obs.KEEP + 5):
        with obs.call("t.ring"):
            pass
    kept = obs.calls()
    assert len(kept) == obs.KEEP
    assert [c.id for c in kept] == list(range(kept[0].id,
                                              kept[0].id + obs.KEEP))
    assert kept[-1].name == "t.ring"


def test_counters_land_on_the_open_span_and_the_call():
    base = obs.totals()["counters"].get("t.n", 0)
    obs.count("t.n", 100)                   # no call open: totals only
    with obs.call("t.count"):
        obs.count("t.n", 2)
        with obs.span("t.a"):
            obs.count("t.n", 3)
            with obs.span("t.b"):
                obs.count("t.n")
    c = _last()
    a, b = c.spans
    assert a.counters == {"t.n": 3} and b.counters == {"t.n": 1}
    assert c.counters == {"t.n": 6}
    assert obs.totals()["counters"]["t.n"] == base + 106


def test_gc_pause_is_counted_on_the_open_span():
    with obs.call("t.gc"):
        with obs.span("t.collect"):
            gc.collect()
    c = _last()
    assert c.spans[0].counters["gc_collections"] >= 1
    assert 0 < c.counters["gc_s"] <= c.spans[0].seconds


def test_totals_sum_span_seconds():
    t = obs.totals()["seconds"].get("t.tot", 0.0)
    with obs.call("t.tot.call"):
        with obs.span("t.tot"):
            time.sleep(0.002)
    assert obs.totals()["seconds"]["t.tot"] - t == pytest.approx(
        _last().spans[0].seconds)


def _sweep():
    spec = CampaignSpec()
    sweep_jax.run_jax_detailed([(spec, s) for s in SEEDS])
    return _last()


@pytest.fixture(scope="module")
def cold_and_warm():
    """A sweep call right after the scan's cache is cleared, and a
    second one of the same shape."""
    sweep_jax._scan_campaigns.clear_cache()
    return _sweep(), _sweep()


def test_compiles_are_counted_on_the_launch_span(cold_and_warm):
    cold, warm = cold_and_warm
    assert cold.name == "engine.call"
    assert cold.counters["compiles"] > 0 and cold.counters["compile_s"] > 0
    (launch,) = [s for s in cold.spans if s.name == "engine.launch"]
    assert launch.counters["compiles"] > 0
    assert warm.counters.get("compiles", 0) == 0


def test_engine_spans_cover_the_call_and_the_scan(cold_and_warm):
    for c in cold_and_warm:
        names = [s.name for s in c.spans]
        assert [n for n in names if n in CALL_STEPS] == list(CALL_STEPS)
        top = sum(s.seconds for s in c.spans if s.parent == c.name)
        assert {s.parent for s in c.spans if s.name in CALL_STEPS} \
            == {"engine.call"}
        assert top >= 0.95 * c.seconds_total
        assert {s.parent for s in c.spans if s.name in SCAN_STEPS} \
            == {"engine.scan"}
        assert c.seconds(*SCAN_STEPS) >= 0.95 * c.seconds("engine.scan")
        assert c.counters["h2d_arrays"] == 1
        assert c.counters["d2h_arrays"] == 1
        assert c.counters["h2d_bytes"] > 0 and c.counters["d2h_bytes"] > 0


def _unscoped_detailed(lane_specs):
    """The sweep as it ran before it had spans: one loop over lanes,
    results then events, the outputs copied straight off the scan."""
    import numpy as np
    prepared = [_prepare(sc, seed) for sc, seed in lane_specs]
    eng = sweep_jax.JaxSweepEngine([lane for _key, lane in prepared])
    args, kw = eng._scan_call()
    out = sweep_jax._scan_campaigns(*args, **kw)
    layout = sweep_jax._out_layout(*sweep_jax._out_sizes(eng.consts))
    eng.out = sweep_jax._unpack(np.asarray(out), layout)["out"]
    return [(eng.lane_results(j), eng.lane_events(j), None)
            for j in range(len(prepared))]


def test_results_are_unchanged_by_spans_and_scopes(monkeypatch):
    """The sweep with its spans and tick scopes gives the same bytes as
    the sweep without either (scopes turned into no-ops, the scan traced
    afresh)."""
    spec = CampaignSpec()
    lanes = [(spec, s) for s in SEEDS]
    got = sweep_jax.run_jax_detailed(lanes)
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    sweep_jax._scan_campaigns.clear_cache()
    try:
        want = _unscoped_detailed(lanes)
        plain = sweep_jax.JaxSweepEngine(
            [_prepare(spec, s)[1] for s in SEEDS]).lower()
    finally:
        sweep_jax._scan_campaigns.clear_cache()
    assert json.dumps(got, sort_keys=True) == json.dumps(want,
                                                         sort_keys=True)
    assert "preempt_sampled" not in plain.as_text(debug_info=True)


def test_lowered_scan_names_the_tick_scopes():
    eng = sweep_jax.JaxSweepEngine([_prepare(CampaignSpec(), s)[1]
                                    for s in SEEDS])
    text = eng.lower().as_text(debug_info=True)
    # name-stack locations read loc("preempt/preempt_sampled/concatenate")
    scopes = set()
    for stack in re.findall(r'loc\("([^"(]*)/[^"/(]*"', text):
        scopes.update(stack.split("/"))
    assert {"poisson", "preempt_to_target", "preempt_sampled", "events",
            "kill", "spawn", "preempt", "topup", "match", "advance", "bill",
            "overhead", "ledger", "accumulate"} <= scopes
