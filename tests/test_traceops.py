"""Trace analytics (core/traceops.py) — the PR's differential tier:

  * streamed collection (``collect="stream"`` through a TraceSink) is
    BYTE-identical to the in-memory ``collect="trace"`` path on all
    three trace engines — pinned on hand-built specs, on all four
    committed goldens, and on the sha256-pinned paper replay,
  * the streaming recorder's window discipline (monotone t, canonical
    per-window ordering) and sink lifecycle are enforced,
  * ``diff_traces`` is empty on self-comparison and detects any
    single-event drop/retime/retarget with the correct divergence t —
    unit fixtures plus a seeded-fuzz tier that upgrades to hypothesis
    where installed (test_sorted_ops.py pattern),
  * the paper-replay vs ``outage_burst()`` diff at seed 2021 is pinned
    as a committed golden (tests/data/paper_vs_outage.diff.json),
  * CLI: ``campaigns diff`` exits 0/1/2 correctly, ``campaigns trace
    --engine jax`` exits 2 with the friendly no-trace line, and
    ``campaigns pareto`` argument errors are regression-covered.
"""
import dataclasses
import gzip
import hashlib
import json
import os
import random

import pytest

from repro.campaigns import main as campaigns_main
from repro.core.api import run
from repro.core.events import CampaignTrace, event_to_dict
from repro.core.spec import CampaignSpec, SetTarget, run_solo
from repro.core.traceops import (CallbackSink, JsonlStreamSink,
                                 StreamingRecorder, TraceDigest,
                                 diff_traces, load_trace, trace_digest)
from tests.engine_equivalence import (HAVE_HYPOTHESIS,
                                      assert_stream_equivalent,
                                      serialized_trace)
from tests.test_events import (NAT_SPEC, PAPER_TRACE_SHA256, SMALL_SPEC)

DATA = os.path.join(os.path.dirname(__file__), "data")
GOLDEN_SPECS = ("paper_replay", "curve_sliced", "workload_curve",
                "dataplane", "outage_burst")
TRACE_ENGINES = ("array", "object", "batched")


def _golden_spec(name: str) -> CampaignSpec:
    with open(os.path.join(DATA, f"{name}.spec.json")) as f:
        return CampaignSpec.from_json(f.read())


def _mutate(trace: CampaignTrace, events) -> CampaignTrace:
    return dataclasses.replace(trace, events=tuple(events))


@pytest.fixture(scope="module")
def small_trace():
    res, _ctl = run_solo(SMALL_SPEC, 7, collect="trace")
    return res.trace


# -- streamed == built: the byte-identity contract -------------------------

def test_stream_equals_trace_bytes_scheduled_mode(tmp_path):
    assert_stream_equivalent(SMALL_SPEC, 7, tmp_path,
                             engines=TRACE_ENGINES)


def test_stream_equals_trace_bytes_nat_mode(tmp_path):
    assert_stream_equivalent(NAT_SPEC, 11, tmp_path,
                             engines=TRACE_ENGINES)


@pytest.mark.parametrize("golden", GOLDEN_SPECS)
def test_stream_equivalent_on_committed_goldens(golden, tmp_path):
    """All three trace engines stream every committed golden campaign
    byte-identically to the in-memory trace; the paper replay's sha256
    must be the pinned one — the sink path can never drift the
    canonical bytes."""
    spec = _golden_spec(golden)
    ref = assert_stream_equivalent(spec, 2021, tmp_path,
                                   engines=TRACE_ENGINES)
    if golden == "paper_replay":
        assert hashlib.sha256(ref.encode()).hexdigest() \
            == PAPER_TRACE_SHA256


def test_stream_through_plain_and_gzip_sinks_roundtrips(tmp_path):
    """A streamed file re-reads (load_trace, .gz transparently) into a
    trace equal to the in-memory one, and streaming never changes the
    summary results."""
    ref = run(SMALL_SPEC, seeds=7, collect="trace")
    for fname in ("t.jsonl", "t.jsonl.gz"):
        path = str(tmp_path / fname)
        res = run(SMALL_SPEC, seeds=7, collect="stream",
                  sink=JsonlStreamSink(path))
        assert res.to_dict() == ref.to_dict()
        got = load_trace(path)
        assert got == ref.trace
        assert diff_traces(ref.trace, got).identical


def test_callback_sink_sees_canonical_event_order():
    seen = []
    headers = []
    sink = CallbackSink(seen.append, on_close=headers.append)
    res = run(SMALL_SPEC, seeds=7, collect="stream", sink=sink)
    ref = run(SMALL_SPEC, seeds=7, collect="trace").trace
    assert seen == list(ref.events)
    assert sink.events_seen == len(ref.events)
    assert headers == [{"schema_version": 1, "kind": "campaign_trace",
                        "name": SMALL_SPEC.name, "seed": 7,
                        "duration_h": SMALL_SPEC.duration_h,
                        "dt_h": SMALL_SPEC.dt_h,
                        "events": len(ref.events)}]
    assert res.trace is None


# -- streaming recorder discipline -----------------------------------------

def test_streaming_recorder_rejects_out_of_order_time():
    rec = StreamingRecorder(CallbackSink(lambda ev: None))
    rec.launched(2.0, 1, "azure", "eastus")
    rec.launched(3.0, 2, "azure", "eastus")    # window advances
    with pytest.raises(ValueError, match="out-of-order"):
        rec.launched(2.5, 3, "azure", "eastus")


def test_streaming_recorder_finish_is_single_shot(tmp_path):
    path = str(tmp_path / "t.jsonl")
    rec = StreamingRecorder(JsonlStreamSink(path))
    rec.launched(0.0, 1, "azure", "eastus")
    n = rec.finish("x", 1, 1.0, 0.25)
    assert n == 1
    with pytest.raises(ValueError, match="finished"):
        rec.finish("x", 1, 1.0, 0.25)
    with pytest.raises(ValueError, match="finished"):
        rec.launched(1.0, 2, "azure", "eastus")
    # the finished file is a valid one-event trace
    t = load_trace(path)
    assert len(t.events) == 1 and t.name == "x"


def test_empty_campaign_streams_a_valid_header_only_trace(tmp_path):
    path = str(tmp_path / "empty.jsonl.gz")
    rec = StreamingRecorder(JsonlStreamSink(path))
    assert rec.finish("empty", 5, 2.0, 0.5) == 0
    t = load_trace(path)
    assert t.events == () and t.seed == 5 and t.duration_h == 2.0


def test_stream_mode_argument_validation(tmp_path):
    with pytest.raises(ValueError, match="sink"):
        run(SMALL_SPEC, seeds=7, collect="stream")          # no sink
    with pytest.raises(ValueError, match="stream"):
        run(SMALL_SPEC, seeds=7,
            sink=CallbackSink(lambda ev: None))             # sink w/o mode
    with pytest.raises(ValueError, match="ONE campaign"):
        run(SMALL_SPEC, seeds=[7, 8], collect="stream",
            sink=CallbackSink(lambda ev: None))             # sweep-shaped
    with pytest.raises(ValueError, match="statistical"):
        run(SMALL_SPEC, seeds=7, engine="jax", collect="stream",
            sink=CallbackSink(lambda ev: None))             # no jax stream


# -- diff_traces: self-identity and mutation detection ---------------------

def test_diff_self_identity(small_trace):
    d = diff_traces(small_trace, small_trace)
    assert d.identical
    assert d.divergence is None and not d.header_changes
    assert d.by_kind == {} and d.entities == {}
    assert all(v == 0 for v in d.deltas().values())


def test_diff_detects_single_event_drop(small_trace):
    i = len(small_trace.events) // 2
    evs = list(small_trace.events)
    dropped = evs.pop(i)
    d = diff_traces(small_trace, _mutate(small_trace, evs))
    assert not d.identical
    assert d.divergence.index == i
    assert d.divergence.t == dropped.t
    assert d.digest_b.events == d.digest_a.events - 1


def test_diff_detects_retime(small_trace):
    evs = list(small_trace.events)
    i = next(j for j, ev in enumerate(evs) if ev.kind == "preempt")
    evs[i] = dataclasses.replace(evs[i], t=evs[i].t + 0.25)
    d = diff_traces(small_trace, _mutate(small_trace, evs))
    assert not d.identical
    assert d.divergence.index <= i
    assert d.divergence.t <= evs[i].t
    assert d.by_kind["preempt"]["changed"] >= 1
    assert d.entities["instances"]["changed"] >= 1


def test_diff_detects_retarget(small_trace):
    evs = list(small_trace.events)
    i = next(j for j, ev in enumerate(evs) if ev.kind == "job_done")
    evs[i] = dataclasses.replace(evs[i], job=10 ** 6)
    d = diff_traces(small_trace, _mutate(small_trace, evs))
    assert not d.identical
    assert d.divergence.index == i
    assert d.divergence.t == small_trace.events[i].t
    assert d.entities["jobs"]["added"] == 1
    assert d.entities["jobs"]["removed"] == 1


def test_diff_reports_header_changes(small_trace):
    other = dataclasses.replace(small_trace, name="renamed", seed=99)
    d = diff_traces(small_trace, other)
    assert not d.identical
    assert d.divergence is None                 # events still equal
    assert d.header_changes == {"name": ("small", "renamed"),
                                "seed": (7, 99)}


def test_diff_digest_reconciles_with_trace_counts(small_trace):
    dig = trace_digest(small_trace)
    counts = small_trace.counts()
    assert dig.events == len(small_trace.events)
    assert dig.launches == counts.get("launch", 0)
    assert dig.preemptions == counts.get("preempt", 0)
    assert dig.jobs_finished == counts.get("job_done", 0)
    assert dig.accel_hours > 0
    assert isinstance(dig, TraceDigest)


def test_diff_to_dict_is_json_stable(small_trace):
    evs = list(small_trace.events)[:-1]
    d = diff_traces(small_trace, _mutate(small_trace, evs))
    payload = json.dumps(d.to_dict(), sort_keys=True)
    assert json.loads(payload) == d.to_dict()
    assert d.to_dict()["identical"] is False
    assert d.to_dict()["divergence"]["index"] == len(evs)


# -- the committed golden diff: paper replay vs outage_burst ---------------

def test_outage_burst_matches_committed_spec():
    from repro.core.scenarios import outage_burst
    assert outage_burst().to_dict() == _golden_spec("outage_burst").to_dict()


def test_paper_vs_outage_diff_matches_golden():
    """The full paper-replay vs outage-burst diff at seed 2021 is
    byte-stable: divergence point, per-kind counts and digest deltas
    can never drift silently.  Regenerate (deliberately) via the
    snippet in tests/data/paper_vs_outage.diff.json's git history."""
    ta = run(_golden_spec("paper_replay"), seeds=2021, engine="batched",
             collect="trace").trace
    tb = run(_golden_spec("outage_burst"), seeds=2021, engine="batched",
             collect="trace").trace
    d = diff_traces(ta, tb)
    with open(os.path.join(DATA, "paper_vs_outage.diff.json")) as f:
        golden = json.load(f)
    assert not d.identical
    assert d.divergence.t == 60.0              # the outage instant
    assert d.to_dict() == golden


# -- property tier: seeded fuzz always, hypothesis where installed ---------

def _random_mutation(rng, trace):
    """One random drop/retime/retarget; returns (mutated, index)."""
    evs = list(trace.events)
    i = rng.randrange(len(evs))
    op = rng.choice(["drop", "retime", "retarget"])
    if op == "drop":
        evs.pop(i)
    elif op == "retime":
        evs[i] = dataclasses.replace(evs[i], t=evs[i].t + 1000.0)
    else:
        ev = evs[i]
        for attr in ("instance", "pilot", "job"):
            if hasattr(ev, attr):
                evs[i] = dataclasses.replace(
                    ev, **{attr: getattr(ev, attr) + 10 ** 7})
                break
        else:
            evs.pop(i)                          # no entity: drop instead
    return _mutate(trace, evs), i


def _first_difference(a, b):
    """The first index where two event lists differ; a length mismatch
    differs at the shorter list's end.  None when they are equal."""
    n = min(len(a), len(b))
    for k in range(n):
        if a[k] != b[k]:
            return k
    return None if len(a) == len(b) else n


def _check_mutation_detected(trace, mutated, i):
    # a mutation at i keeps the prefix before i; it can leave i itself
    # unchanged too, when the dropped event equals its neighbour
    k = _first_difference(trace.events, mutated.events)
    assert k is not None and k >= i
    d = diff_traces(trace, mutated)
    assert not d.identical
    assert d.divergence is not None
    assert d.divergence.index == k
    # the reported first-divergence time is the mutated position's
    # canonical time (or earlier, when the reorder bubbles it up)
    assert d.divergence.t <= max(ev.t for ev in trace.events)


def test_diff_drop_of_duplicate_event_diverges_after_it():
    """Two equal scale events at t=0: dropping the first leaves index 0
    unchanged, so the first divergence is the length mismatch at 1."""
    spec = CampaignSpec(name="a", capacity_scale=0.5, spot=False,
                        price_scale=0.8, budget=2000.0,
                        budget_floor_fraction=0.1, downscale_target=0,
                        duration_h=12.0, job_wall_h=1.0, min_queue=500,
                        timeline=(SetTarget(0.0, 0), SetTarget(0.0, 0)))
    trace = run_solo(spec, 0, collect="trace")[0].trace
    assert len(trace.events) == 2
    assert trace.events[0] == trace.events[1]
    mutated, i = _random_mutation(random.Random(1), trace)
    assert i == 0 and len(mutated.events) == 1
    d = diff_traces(trace, mutated)
    assert d.divergence.index == 1
    assert d.divergence.b is None
    _check_mutation_detected(trace, mutated, i)


def test_diff_seeded_fuzz_identity_and_mutations(small_trace):
    """Deterministic fallback tier: runs everywhere, hypothesis or
    not."""
    rng = random.Random(20210807)
    assert diff_traces(small_trace, small_trace).identical
    for _ in range(25):
        mutated, i = _random_mutation(rng, small_trace)
        _check_mutation_detected(small_trace, mutated, i)


if HAVE_HYPOTHESIS:
    from hypothesis import given, settings, HealthCheck
    import hypothesis.strategies as st
    from tests.engine_equivalence import spec_strategy

    @settings(max_examples=15, deadline=None,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.data_too_large])
    @given(spec=spec_strategy(), seed=st.integers(0, 2 ** 20),
           data=st.data())
    def test_diff_property_identity_and_mutation(spec, seed, data):
        res, _ctl = run_solo(spec, seed, collect="trace")
        t = res.trace
        assert diff_traces(t, t).identical
        if not t.events:
            return
        mut_seed = data.draw(st.integers(0, 2 ** 31))
        mutated, i = _random_mutation(random.Random(mut_seed), t)
        _check_mutation_detected(t, mutated, i)
else:                                            # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed; seeded-fuzz "
                             "tier above covers the property")
    def test_diff_property_identity_and_mutation():
        pass


# -- CLI regressions -------------------------------------------------------

@pytest.fixture()
def small_spec_file(tmp_path):
    p = tmp_path / "small.spec.json"
    p.write_text(SMALL_SPEC.to_json())
    return str(p)


def test_cli_trace_jax_engine_exits_2_with_friendly_line(
        small_spec_file, capsys):
    rc = campaigns_main(["trace", small_spec_file, "--engine", "jax"])
    err = capsys.readouterr().err
    assert rc == 2
    assert err.startswith("error:")
    assert "statistical" in err and "trace-capable" in err


def test_cli_trace_stream_flag_writes_identical_bytes(
        small_spec_file, tmp_path, capsys):
    a = str(tmp_path / "a.jsonl")
    b = str(tmp_path / "b.jsonl")
    assert campaigns_main(["trace", small_spec_file, "--seed", "7",
                           "--out", a]) == 0
    assert campaigns_main(["trace", small_spec_file, "--seed", "7",
                           "--out", b, "--stream"]) == 0
    with open(a, "rb") as fa, open(b, "rb") as fb:
        assert fa.read() == fb.read()
    assert "(streamed)" in capsys.readouterr().err


def test_cli_trace_stream_without_out_exits_2(small_spec_file, capsys):
    rc = campaigns_main(["trace", small_spec_file, "--stream"])
    assert rc == 2
    assert "--out" in capsys.readouterr().err


def test_cli_diff_exit_codes_and_json(small_spec_file, tmp_path, capsys):
    a = str(tmp_path / "a.jsonl")
    b = str(tmp_path / "b.jsonl.gz")
    campaigns_main(["trace", small_spec_file, "--seed", "7", "--out", a])
    campaigns_main(["trace", small_spec_file, "--seed", "8", "--out", b])
    capsys.readouterr()

    assert campaigns_main(["diff", a, a]) == 0
    assert "identical" in capsys.readouterr().out

    out_json = str(tmp_path / "d.json")
    assert campaigns_main(["diff", a, b, "--json", out_json]) == 1
    assert "first divergence" in capsys.readouterr().out
    with open(out_json) as f:
        payload = json.load(f)
    assert payload["identical"] is False
    assert payload["divergence"]["index"] >= 0

    # --json - : machine payload on stdout, summary on stderr
    assert campaigns_main(["diff", a, b, "--json", "-"]) == 1
    cap = capsys.readouterr()
    assert json.loads(cap.out)["kind"] == "trace_diff"
    assert "first divergence" in cap.err


def test_cli_diff_bad_file_exits_2(tmp_path, capsys):
    missing = str(tmp_path / "nope.jsonl")
    rc = campaigns_main(["diff", missing, missing])
    assert rc == 2
    assert "error:" in capsys.readouterr().err


def test_cli_diff_non_trace_file_exits_2(tmp_path, capsys):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"kind": "not_a_trace"}\n')
    rc = campaigns_main(["diff", str(bad), str(bad)])
    assert rc == 2
    assert "error:" in capsys.readouterr().err


def test_cli_pareto_bad_axis_exits_2(small_spec_file, capsys):
    rc = campaigns_main(["pareto", small_spec_file, "--y", "nonsense"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "error:" in err and "nonsense" in err
