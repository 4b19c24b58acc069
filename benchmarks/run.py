"""Benchmark harness — one function per paper table/figure + framework
benches. Prints ``name,us_per_call,derived`` CSV rows (with detail blocks
on indented lines below each row).

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run --list
    PYTHONPATH=src python -m benchmarks.run --only campaign
    PYTHONPATH=src python -m benchmarks.run --only sweep --json BENCH.json

``--json PATH`` additionally writes
``{schema_version, benches: {name: {us_per_call, derived}}}`` so the
perf trajectory stays machine-comparable across PRs (the committed
``BENCH_sweep.json`` / ``BENCH_sweep_jax.json`` are the sweep-engine
baselines; CI uploads fresh ones per run as artifacts).  Benches that
declare an acceptance bar (the sweep engines' speedups) additionally
report ``{"bar": <threshold>, "pass": <derived >= bar>}`` — CI fails
the sweep smoke when ``pass`` is false (``--check-bars`` makes any
failed bar a non-zero exit).  Consumers should check ``schema_version``
(currently 2; version 1 was the bare ``{name: ...}`` mapping — bar/pass
are additive to 2).
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

from repro.compile_cache import use_compile_cache

BENCH_SCHEMA_VERSION = 2

#: acceptance bars on a bench's ``derived`` value (see each bench's
#: docstring for the configuration the bar is defined at)
BENCH_BARS = {
    "sweep_campaign_speedup": 10.0,   # batched numpy vs sequential, B=64
    "sweep_jax_speedup": 3.0,         # compiled jax vs batched, B=512
}


def _benches():
    from benchmarks import fleet_scale as fs
    from benchmarks import framework_benches as fb
    from benchmarks import paper_tables as pt
    from benchmarks import sweep_jax_scale as sjs
    from benchmarks import sweep_scale as ss

    return [
        ("fleet_tick_speedup", fs.bench_fleet_tick_throughput),
        ("sweep_campaign_speedup", ss.bench_sweep_throughput),
        ("sweep_jax_speedup", sjs.bench_sweep_jax_throughput),
        ("fig1_fleet_timeline", pt.bench_fig1_fleet_timeline),
        ("fig2_gpu_hours_doubling", pt.bench_fig2_gpu_hours_doubling),
        ("claims_table_maxerr_pct", pt.bench_claims_table),
        ("preemption_economics", pt.bench_preemption_economics),
        ("budget_control_latency", pt.bench_budget_control),
        ("nat_keepalive_drops", pt.bench_nat_keepalive),
        ("overlay_matches_per_s", pt.bench_overlay_throughput),
        ("elastic_restart_steps", fb.bench_elastic_train_restart),
        ("kernels_max_err", fb.bench_kernels),
        ("roofline_cells_ok", fb.bench_roofline_table),
    ]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run benches whose name contains this substring")
    ap.add_argument("--list", action="store_true",
                    help="print registered bench names and exit")
    ap.add_argument("--json", default=None,
                    help="also write {name: {us_per_call, derived}} here")
    ap.add_argument("--check-bars", action="store_true",
                    help="exit non-zero if any bench with a declared "
                         "acceptance bar reports pass=false")
    args = ap.parse_args()

    benches = _benches()
    if args.list:
        for name, _fn in benches:
            bar = BENCH_BARS.get(name)
            print(name if bar is None else f"{name} (bar >= {bar:g}x)")
        return
    if args.only:
        benches = [(n, f) for n, f in benches if args.only in n]
        if not benches:
            print(f"unknown bench filter {args.only!r}: matches no "
                  "registered bench (see --list)", file=sys.stderr)
            raise SystemExit(2)

    use_compile_cache()
    print("name,us_per_call,derived")
    report = {}
    failures = 0
    barfails = []
    for name, fn in benches:
        try:
            us, derived, rows = fn()
            print(f"{name},{us:.1f},{derived}")
            for r in rows:
                print(r)
            report[name] = {"us_per_call": round(us, 1), "derived": derived}
            bar = BENCH_BARS.get(name)
            if bar is not None:
                ok = isinstance(derived, (int, float)) and derived >= bar
                report[name]["bar"] = bar
                report[name]["pass"] = bool(ok)
                if not ok:
                    barfails.append(f"{name}: derived {derived} < "
                                    f"bar {bar:g}")
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},NaN,ERROR")
            traceback.print_exc(limit=5)
            report[name] = {"us_per_call": None, "derived": "ERROR"}
            if name in BENCH_BARS:
                report[name]["bar"] = BENCH_BARS[name]
                report[name]["pass"] = False
                barfails.append(f"{name}: ERROR")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema_version": BENCH_SCHEMA_VERSION,
                       "benches": report},
                      f, indent=2, sort_keys=True, default=str)
            f.write("\n")
        print(f"# wrote {args.json}", file=sys.stderr)
    if args.check_bars and barfails:
        for line in barfails:
            print(f"bar failed: {line}", file=sys.stderr)
        raise SystemExit(1)
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
