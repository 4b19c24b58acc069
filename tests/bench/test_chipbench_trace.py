"""The trace reduction's arithmetic on a small synthetic trace, the
readers that turn it into per-layer metrics, and the file reader on a
trace recorded here."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import bench, cells, spans, trace  # noqa: E402

# device ops and host spans in ns: one call from 50 to 700
EVENTS = trace.Events(
    device=[[("fusion.1", 100, 200), ("campaign_preempt.17", 150, 250),
             ("while.5", 300, 400), ("campaign_bill.8", 600, 650),
             ("before", 0, 40)]],
    host=[("sweep.call", 50, 700), ("sweep.scan", 100, 450),
          ("sweep.prepare", 460, 470), ("sweep.bake", 480, 590),
          ("sweep.events", 640, 690)])


def reduced():
    return trace.reduce(EVENTS, spans.CALL, list(spans.WRAPPED))


def test_op_names_from_hlo_text():
    text = ("%campaign_preempt.17 = s32[320,128]{1,0:T(8,128)S(1)} "
            "custom-call(s32[320,128]{1,0} %pad.108), "
            'custom_call_target="tpu_custom_call"')
    assert trace.op_name(text) == "campaign_preempt.17"
    assert trace.op_kind("campaign_preempt.17") == "campaign_preempt"
    assert trace.op_kind("while") == "while"


def test_interval_helpers():
    assert trace.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert trace.overlap([(1, 4), (5, 7)], [(3, 6)]) == 2
    assert trace.gaps([(2, 3), (5, 6)], (0, 8)) == [(0, 2), (3, 5), (6, 8)]
    assert trace.clip([(0, 3), (5, 9)], (2, 6)) == [(2, 3), (5, 6)]


def test_reduce_busy_idle_and_spans():
    r = reduced()
    assert r.window_s == pytest.approx(650e-9)
    # busy: [100,250] + [300,400] + [600,650]; the op before the window
    # is left out
    assert r.busy_s == pytest.approx(300e-9)
    assert r.op_seconds == pytest.approx(
        {"fusion.1": 100e-9, "campaign_preempt.17": 100e-9,
         "while.5": 100e-9, "campaign_bill.8": 50e-9})
    assert r.in_span_busy_s["sweep.scan"] == pytest.approx(250e-9)
    assert r.in_span_busy_s["sweep.bake"] == 0.0
    assert r.span_count["sweep.scan"] == 1
    # gaps: [400,600] mostly inside the bake, then [50,100] (none
    # open), [250,300] inside the scan, [650,700] inside the events
    assert [n for n, _ in r.idle_gaps] == ["sweep.bake", "host",
                                           "sweep.scan", "sweep.events"]
    assert [s for _, s in r.idle_gaps] == pytest.approx(
        [200e-9, 50e-9, 50e-9, 50e-9])
    b = r.breakdown()
    assert b["device_ops"][0][1] == pytest.approx(100e-9)
    assert b["device_ops"][-1] == ["campaign_bill.8", pytest.approx(50e-9)]


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError):
        trace.reduce(trace.Events(device=[[]], host=EVENTS.host),
                     spans.CALL, [])


def test_readers_on_the_reduction():
    run = bench.Run()
    run.trace = reduced()
    run.shape = {"B": 2, "G": 3, "W": 4, "L": 2, "P": 2, "N": 5,
                 "dp_gating": False, "dp_staging": False, "nat_any": False}
    run.peak = {"hbm_bytes_per_s": 1e12}
    run.kernels = cells.kernel_modules()
    read = {n: cells.load_module("metrics", n).read
            for n in ("device_idle_pct", "scan_roofline",
                      "tick_kernels_roofline")}
    assert read["device_idle_pct"](run) == pytest.approx(
        100 * (1 - 300 / 650))
    # one scan of 5 ticks x 718 bytes at 1e12 B/s, over 250 ns busy
    assert read["scan_roofline"](run) == pytest.approx(
        100 * 5 * 718 / 1e12 / 250e-9)
    # preempt 624 + match 56 + advance 312 + bill 96 bytes a tick, 5
    # ticks, over the kernels' 150 ns
    assert read["tick_kernels_roofline"](run) == pytest.approx(
        100 * 5 * (624 + 56 + 312 + 96) / 1e12 / 150e-9)
    run.trace = None
    assert all(f(run) is None for f in read.values())


def test_host_span_readers():
    run = bench.Run()
    timer = spans.Spans()
    for d in ({"sweep.prepare": 0.01, "sweep.bake": 0.02,
               "sweep.scan": 1.0, "sweep.results": 0.003},
              {"sweep.prepare": 0.03, "sweep.bake": 0.04,
               "sweep.scan": 3.0, "sweep.events": 0.005}):
        timer.begin_call()
        timer._cur.update(d)
        timer.end_call()
    run.span_calls = timer.take()
    assert timer.calls == []
    get = lambda n: cells.load_module("metrics", n).read(run)  # noqa
    assert get("prep_ms") == pytest.approx(50.0)
    assert get("scan_ms") == pytest.approx(2000.0)
    assert get("replay_ms") == pytest.approx(4.0)
    run.calls = [{"t0": 1.0, "t1": 2.0, "lanes": 32},
                 {"t0": 2.0, "t1": 4.0, "lanes": 32}]
    run.window_start = 1.0
    assert get("campaigns_per_s") == pytest.approx(64 / 3.0)
    assert get("sweep_s_p90") == pytest.approx(1.9)
    run.span_calls = None
    assert get("prep_ms") is None and get("scan_ms") is None


def test_span_wrappers_time_the_program_functions():
    """Installed, a wrapper times its function into the open call; taken
    out, the function is the program's again."""
    from repro.core import sweep_jax
    before = sweep_jax._prepare
    timer = spans.Spans()
    timer.install()
    try:
        assert sweep_jax._prepare is not before
        timer.begin_call()
        with pytest.raises(Exception):
            sweep_jax._prepare()            # a bad call is timed too
        timer.end_call()
    finally:
        timer.uninstall()
    assert sweep_jax._prepare is before
    (call,) = timer.take()
    assert set(call) == {"sweep.prepare"} and call["sweep.prepare"] > 0


def test_read_xplane_finds_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.ones(8)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(spans.CALL):
        with jax.profiler.TraceAnnotation("sweep.scan"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.read_xplane(trace.find_xplane(str(tmp_path)),
                           [spans.CALL, "sweep.scan"])
    names = sorted(n for n, _s, _e in ev.host)
    assert names == [spans.CALL, "sweep.scan"]
    (_n, s0, e0), = [h for h in ev.host if h[0] == spans.CALL]
    (_n, s1, e1), = [h for h in ev.host if h[0] == "sweep.scan"]
    assert s0 <= s1 < e1 <= e0
