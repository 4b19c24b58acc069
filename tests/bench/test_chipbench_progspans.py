"""The per-layer metrics read from the program's own spans and counters
(``progspans.py``, ``metrics/{put,launch,fetch,gc}_ms.py``): the window
selection and the readers on synthetic records, a program without the
recorder, a window driven through the harness on the CPU, names kept
apart from the harness's own spans, and the program's annotations in a
profile."""
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import bench, cells, progspans, spans, trace  # noqa: E402
from repro import obs  # noqa: E402

READERS = ("put_ms", "launch_ms", "fetch_ms", "gc_ms")
SEED = 2 ** 31 + 2021


def _read(name, run):
    return cells.load_module("metrics", name).read(run)


def _record(t0, t1, put, launch, fetch, gc_s=None):
    c = obs.Call(0, progspans.CALL, t0)
    c.t1 = t1
    t = t0
    for name, s in (("engine.put", put), ("engine.launch", launch),
                    ("engine.fetch", fetch)):
        sp = obs.Span(name, "engine.scan", t)
        sp.end = t + s
        c.spans.append(sp)
        t += s
    if gc_s is not None:
        c.counters["gc_s"] = gc_s
    return c


def _run():
    run = bench.Run()
    run.window_start = 10.0
    run.calls = [{"t0": 10.0, "t1": 11.0, "lanes": 4},
                 {"t0": 11.0, "t1": 12.0, "lanes": 4}]
    return run


def test_readers_take_the_untraced_window_only(monkeypatch):
    recs = [
        _record(9.0, 9.9, 0.5, 0.5, 0.5, gc_s=1.0),      # the warm-up
        _record(10.0, 11.0, 0.002, 0.0001, 0.010),
        _record(11.0, 12.0, 0.004, 0.0003, 0.014, gc_s=0.04),
        _record(12.1, 12.5, 0.5, 0.5, 0.5, gc_s=1.0),    # traced calls
        _record(11.5, 12.2, 0.5, 0.5, 0.5, gc_s=1.0),    # ends too late
    ]
    monkeypatch.setattr(progspans, "records", lambda: recs)
    run = _run()
    assert progspans.in_window(run) == recs[1:3]
    got = {n: _read(n, run) for n in READERS}
    assert got == pytest.approx({"put_ms": 3.0, "launch_ms": 0.2,
                                 "fetch_ms": 12.0, "gc_ms": 20.0})
    run.calls = []
    assert all(_read(n, run) is None for n in READERS)


def test_records_are_the_sweep_calls_only():
    with obs.call("t.other"):
        pass
    assert all(c.name == progspans.CALL for c in progspans.records())


def test_readers_are_silent_on_a_program_without_the_recorder(monkeypatch):
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert progspans.records() == []
    assert all(_read(n, _run()) is None for n in READERS)


def test_program_span_names_are_not_the_harness_spans():
    from repro.core import sweep_jax
    import inspect
    src = inspect.getsource(sweep_jax)
    names = {n for n in ("engine.call", "engine.prepare", "engine.bake",
                         "engine.scan", "engine.results", "engine.events",
                         "engine.put", "engine.launch", "engine.wait",
                         "engine.fetch") if f'"{n}"' in src}
    assert len(names) == 10
    assert not names & (set(spans.WRAPPED) | {spans.CALL})
    assert progspans.CALL in names


@pytest.fixture
def small_cell_on_cpu(monkeypatch):
    """A driven run skips the harness's look for a chip and runs its
    cell at a size a test can hold, with JAX's persistent cache off."""
    import jax
    from repro import compile_cache
    from test_chipbench_correct import small
    monkeypatch.setattr(bench, "chips", lambda n: jax.devices())
    monkeypatch.setattr(cells, "resolve", small)
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "off")
    keep = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", keep)


def test_a_driven_window_reads_the_program_spans(small_cell_on_cpu,
                                                monkeypatch):
    """The harness's traced window on the CPU: the readers find the
    program's calls there, and the program's scan steps add up to what
    the harness's ``sweep.scan`` wrapper timed.  A CPU profile has no
    TPU plane, so each traced call stands in for the chip's device
    ops."""
    read = trace.read_xplane

    def with_device_ops(path, names):
        ev = read(path, names)
        ev.device = [[("stand-in", s, e) for n, s, e in ev.host
                      if n == spans.CALL]]
        return ev
    monkeypatch.setattr(trace, "read_xplane", with_device_ops)
    c = bench.Cell("paper.seeds-32")
    c.setup(time.perf_counter(), SEED)
    run = c.window(SEED, 2.0, traced=True)
    got = progspans.in_window(run)
    assert len(got) == len(run.calls) == len(run.span_calls) >= 2
    assert all(_read(n, run) is not None for n in READERS)
    assert _read("put_ms", run) > 0 and _read("fetch_ms", run) > 0
    assert _read("gc_ms", run) >= 0
    scan = sum(h["sweep.scan"] for h in run.span_calls)
    steps = sum(r.seconds("engine.put", "engine.launch", "engine.wait",
                          "engine.fetch") for r in got)
    ours = sum(r.seconds("engine.scan") for r in got)
    assert ours == pytest.approx(scan, rel=0.05)
    assert steps == pytest.approx(scan, rel=0.05)


def test_program_annotations_nest_in_the_harness_scan(tmp_path):
    """On a CPU profile the program's scan steps are annotations inside
    the harness's ``sweep.scan`` annotation (which its ``engine.scan``
    holds), and the trace reader keeps them when their names are
    passed."""
    import jax
    from repro.core import sweep_jax
    from repro.core.spec import CampaignSpec
    from repro.core.sweep import _prepare
    eng = sweep_jax.JaxSweepEngine([_prepare(CampaignSpec(), s)[1]
                                    for s in (1, 2)])
    eng.run()                                   # compiled outside the trace
    timer = spans.Spans()
    timer.install()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(spans.CALL):
            sweep_jax.run_jax_detailed([(CampaignSpec(), s) for s in (1, 2)])
    finally:
        jax.profiler.stop_trace()
        timer.uninstall()
    steps = ["engine.put", "engine.launch", "engine.wait", "engine.fetch"]
    ev = trace.read_xplane(trace.find_xplane(str(tmp_path)),
                           [spans.CALL, "sweep.scan", "engine.scan"] + steps)
    got = {}
    for n, s, e in ev.host:
        got.setdefault(n, []).append((s, e))
    (scan,) = got["sweep.scan"]
    (ours,) = got["engine.scan"]
    assert ours[0] <= scan[0] <= scan[1] <= ours[1]
    for n in steps:
        ((s, e),) = got[n]
        assert scan[0] <= s <= e <= scan[1], n
