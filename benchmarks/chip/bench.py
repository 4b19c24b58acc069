"""Run one cell once: set up, measure for ``--seconds`` (or trace a
window of whole calls), check the answers against the plain reference,
print the result line.

End-to-end metrics come from the untraced run (``--trace 0``) and
per-layer metrics from the traced run (``--trace 1``); each is read by
``metrics/<name>.py`` from the :class:`Run` below.  A traced run times
its host spans over the same untraced window, with the profiler off,
and then traces ``trace_calls`` whole calls for the device metrics, with
the profiler's Python and host tracers at their minimum, so that its
cost stays out of the host spans.
"""
from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import time
from typing import List, Optional

import numpy as np

from benchmarks.chip import cells, correct, spans, trace, traffic

#: ``jax.monitoring`` duration events that count as compile work
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class NoChip(RuntimeError):
    """The machine holds no TPU, or fewer chips than the cell asks for."""


class Run:
    """What a metric reader may read."""

    def __init__(self):
        self.calls: List[dict] = []        # {"t0", "t1", "lanes"} each
        self.window_start = 0.0
        self.setup_s = 0.0
        self.compile_s = 0.0
        # host seconds by span name, one dict per call of the untraced
        # window (traced runs only)
        self.span_calls: Optional[List[dict]] = None
        self.trace: Optional[trace.Reduction] = None
        self.shape: dict = {}
        self.peak: dict = {}
        self.kernels: list = []


class CompileLog:
    """Sums the compile events that ``jax.monitoring`` reports."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def __call__(self, name, seconds, **_kw):
        if name in COMPILE_EVENTS:
            self.seconds += seconds
            self.count += 1


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def chips(n: int) -> list:
    """JAX's devices, or :class:`NoChip` unless they are ``n`` TPU
    chips or more."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"needs {n} TPU chip(s); JAX finds "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def profile_options():
    """The profiler with its Python tracer off and its host tracer at
    level 1, which keeps the ``TraceAnnotation`` spans and drops the
    runtime's own host events."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def cell_shape(config: dict, lanes: int) -> dict:
    """Logical shapes of one call, worked out by the reference from the
    spec (not taken from the program)."""
    from benchmarks.chip.reference import Lane
    ln = Lane(config["spec"], 0, config["catalog_tables"])
    return {"B": lanes, "G": ln.G, "W": ln.W, "L": ln.L,
            "P": len(ln.providers), "N": ln.N,
            "dp_gating": ln.dp_gating, "dp_staging": ln.dp_staging}


class Cell:
    """One cell's set-up, timed window and verdict, in one process."""

    def __init__(self, workload: str):
        self.workload = workload
        self.cell = cells.resolve(workload)
        self.conf, self.mix = self.cell["config"], self.cell["traffic"]

    def setup(self, t_start: float, seed: int):
        """Imports, the device, the cell's specs and one warm-up call at
        the window's exact width; returns the first device."""
        t = time.perf_counter()
        import jax
        from jax import monitoring
        t_import = time.perf_counter() - t

        t = time.perf_counter()
        devs = chips(self.cell["cell"]["chips"])
        self.dev, self.n_devices = devs[0], len(devs)
        t_devices = time.perf_counter() - t

        from repro.compile_cache import use_compile_cache
        from repro.core import api
        from repro.core.spec import CampaignSpec
        cache_dir = use_compile_cache()
        # every program of the cell goes to the cache, however quick its
        # compile, so that a second run compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.compiles = CompileLog()
        monitoring.register_event_duration_secs_listener(self.compiles)

        t = time.perf_counter()
        self.spec_dicts = traffic.variants(self.conf, self.mix)
        specs = [CampaignSpec.from_dict(s) for s in self.spec_dicts]
        self.lanes = traffic.lanes_per_call(self.conf, self.mix)
        t_build = time.perf_counter() - t

        def call(seeds):
            return api.sweep(specs, seeds, engine="jax").rows
        self.call = call

        t = time.perf_counter()
        warm = traffic.SeedSchedule(seed, self.mix["seeds_per_call"])
        call(warm.next_call())
        t_first = time.perf_counter() - t
        self.setup_s = time.perf_counter() - t_start
        self.setup_compile_s = self.compiles.seconds
        self.setup_compiles = self.compiles.count
        log(f"setup: device={self.dev.platform}/{self.dev.device_kind} "
            f"import_s={t_import} device_init_s={t_devices} "
            f"build_s={t_build} first_call_s={t_first} "
            f"compile_s={self.compiles.seconds} "
            f"compile_events={self.compiles.count} "
            f"setup_s={self.setup_s} cache={cache_dir}")
        return self.dev

    def _loop(self, schedule, more, record: List[dict],
              timer: Optional[spans.Spans]) -> bool:
        """Closed-loop calls while ``more(record)``; each call's times go
        to ``record`` and its rows to ``self.done``.  With a ``timer``
        each call is a ``sweep.call`` span and its host spans are
        recorded.  Returns False when a call failed (which ends it)."""
        import jax
        while more(record):
            seeds = schedule.next_call()
            self.attempted += self.lanes
            t0 = time.perf_counter()
            try:
                if timer is None:
                    rows = self.call(seeds)
                else:
                    timer.begin_call()
                    with jax.profiler.TraceAnnotation(spans.CALL):
                        rows = self.call(seeds)
                    timer.end_call()
            except Exception as e:
                self.failed += self.lanes
                log(f"call failed: {type(e).__name__}: {e}")
                return False
            t1 = time.perf_counter()
            record.append({"t0": t0, "t1": t1, "lanes": len(rows)})
            self.done.append((self.spec_dicts,
                              [correct.slim(r) for r in rows]))
        return True

    def window(self, seed: int, seconds: float, traced: bool) -> Run:
        """Closed-loop calls for ``seconds``.  Traced: the same window
        with the host spans timed and the profiler off, then
        ``trace_calls`` whole calls under the profiler.  Lane seeds
        follow the warm-up's in the seed's schedule."""
        import jax
        run = Run()
        run.setup_s = self.setup_s
        run.compile_s = self.setup_compile_s
        schedule = traffic.SeedSchedule(seed, self.mix["seeds_per_call"])
        schedule.next_call()                    # the warm-up's seeds
        self.done = []                          # (spec dicts, slim rows)
        self.attempted = self.failed = 0
        timer = spans.Spans() if traced else None
        traced_calls: List[dict] = []
        n_traced = int(self.mix["trace_calls"])
        trace_dir = None
        if timer is not None:
            timer.install()
        run.window_start = time.perf_counter()
        deadline = run.window_start + seconds
        try:
            ok = self._loop(schedule,
                            lambda _done: time.perf_counter() < deadline,
                            run.calls, timer)
            if timer is not None:
                run.span_calls = timer.take()
            if traced and ok:
                trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=profile_options())
                try:
                    self._loop(schedule,
                               lambda done: len(done) < n_traced,
                               traced_calls, timer)
                finally:
                    jax.profiler.stop_trace()
        finally:
            if timer is not None:
                timer.uninstall()
        end = run.calls[-1]["t1"] if run.calls else run.window_start
        log(f"window: calls={len(run.calls)} lanes_per_call={self.lanes} "
            f"seconds={end - run.window_start} "
            f"compile_events={self.compiles.count - self.setup_compiles}")
        stats = self.dev.memory_stats() or {}
        self.device = {"platform": self.dev.platform,
                       "kind": self.dev.device_kind,
                       "count": self.n_devices,
                       "memory_peak_bytes":
                           int(stats.get("peak_bytes_in_use", 0))}
        if traced_calls:
            t = time.perf_counter()
            ev = trace.read_xplane(trace.find_xplane(trace_dir),
                                   list(spans.WRAPPED) + [spans.CALL])
            shutil.rmtree(trace_dir, ignore_errors=True)
            run.trace = trace.reduce(ev, spans.CALL, list(spans.WRAPPED))
            self.device["busy_s"] = run.trace.busy_s
            self.device["window_s"] = run.trace.window_s
            native = {k: n for k, n in run.trace.op_count.items()
                      if any(trace.op_kind(k) in m.EVENT_NAMES
                             for m in cells.kernel_modules())}
            log(f"trace: read_s={time.perf_counter() - t} "
                f"device_ops={len(run.trace.op_seconds)} "
                f"kernel_events={native} spans={run.trace.span_count}")
            self._log_trace_cost(run, traced_calls, timer.take())
        return run

    def _log_trace_cost(self, run: Run, traced_calls: List[dict],
                        traced_spans: List[dict]):
        """What the profiler adds to a call: the mean call and its host
        spans outside the scan, untraced and traced, beside the device's
        busy time per traced call."""
        def call_ms(calls):
            return 1e3 * float(np.mean([c["t1"] - c["t0"] for c in calls]))

        def host_ms(span_calls):
            names = [n for n in spans.WRAPPED if n != "sweep.scan"]
            return float(np.mean(spans.per_call_ms(span_calls, *names)))
        log(f"trace cost: call_ms untraced={call_ms(run.calls)} "
            f"traced={call_ms(traced_calls)} "
            f"host_ms_outside_scan untraced={host_ms(run.span_calls)} "
            f"traced={host_ms(traced_spans)} "
            f"device_busy_ms_per_traced_call="
            f"{1e3 * run.trace.busy_s / len(traced_calls)}")

    def compare(self, seed: int):
        """Draws the lanes from the seed and runs the reference; returns
        the numbers, the drawn lanes and the reference rows."""
        gc.collect()            # the program's state is freed first
        t = time.perf_counter()
        drawn = correct.draw(self.done, int(self.mix["check_lanes"]), seed)
        nums, want = correct.check(drawn, self.conf["catalog_tables"])
        log(f"reference: lanes={len(drawn)} "
            f"seconds={time.perf_counter() - t}")
        return nums, drawn, want

    def metrics(self, run: Run, traced: bool) -> dict:
        run.shape = cell_shape(self.conf, self.lanes)
        if traced:
            peaks = json.loads(
                (cells.HERE / "peaks.json").read_text())["devices"]
            if self.dev.device_kind not in peaks:
                raise KeyError(f"no peaks for device kind "
                               f"{self.dev.device_kind!r} in peaks.json")
            run.peak = peaks[self.dev.device_kind]
            run.kernels = cells.kernel_modules()
        out = {}
        for m in self.cell["per_layer" if traced else "end_to_end"]:
            value = cells.load_module("metrics", m["name"]).read(run)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             t_start: float) -> dict:
    """One run of one cell; returns the result line's object."""
    c = Cell(workload)
    c.setup(t_start, seed)
    run = c.window(seed, seconds, traced)
    nums, drawn, _want = c.compare(seed)
    ok, checks = correct.judge(nums, c.cell["limits"])
    ok = ok and c.failed == 0 and bool(drawn)
    out = {"correct": bool(ok), "attempted": c.attempted,
           "failed": c.failed, "metrics": c.metrics(run, traced),
           "device": c.device}
    if run.trace is not None:
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = checks
    for name, chk in checks.items():
        log(f"check {name}={chk['value']} limit={chk['limit']}")
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        out = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                       t_start)
    except NoChip as e:
        log(f"chipbench: {e}")
        return 3
    print(json.dumps(out), flush=True)
    return 0
