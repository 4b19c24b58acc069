"""Bring-up smoke for the compiled campaign sweep on one TPU chip.

    python chip_smoke.py

Drives ``api.sweep(specs, seeds, engine="jax")`` -- the planner's main
path -- at planning-grid width (512 lanes of full 336 h campaigns) with
the Pallas tick kernels compiled natively, and checks what comes out:

  (a) the paper replay (``CampaignSpec()``) x seeds 0..511, cold then warm;
  (b) ``scenarios.dataplane_burst()`` x 512 seeds (the data-plane
      variants of the scan), cold then warm;
  (c) the tick kernels against their jnp oracles on the chip, then the
      lanes of (a) again with the oracles in place of the kernels
      (``use_pallas=False``): per lane, counts equal and cost/GPU-days
      within 1e-5 relative;
  (d) the batched numpy engine on the host over 64 of the seeds: the
      jax means and [p5, p95] bands of (a) and (b) sit within
      ``sweep_jax.STAT_BANDS`` of it.

One process, no children.  It refuses to run anywhere but a TPU: a
number from the CPU is never reported as the chip's.  Every phase and
check prints a line; the last line is one JSON object naming the
device, printed only when every check passed.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

LANES = 512              # planning-grid width (ROADMAP S1's B=512 cell)
HOST_LANES = 64          # batched numpy reference lanes (phase d)
REL_TOL = 1e-5           # kernel vs oracle float totals, relative
COUNT_KEYS = ("jobs_finished", "preemptions", "nat_drops")
FLOAT_KEYS = ("cost", "accel_days")


class Checks:
    """Prints every check as it is made and remembers the failures."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"check {name}: {'ok' if ok else 'FAILED'}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return ok


def timed_sweep(spec, seeds, label):
    """Cold then warm ``api.sweep(engine="jax")``; rows come back as
    host values, so each wall time includes the readback."""
    from repro.core import api
    walls = []
    for _ in ("cold", "warm"):
        t0 = time.perf_counter()
        res = api.sweep([spec], seeds, engine="jax")
        walls.append(time.perf_counter() - t0)
    print(f"phase {label}: lanes={len(seeds)} cold_s={walls[0]:.3f} "
          f"warm_s={walls[1]:.3f}", flush=True)
    return res


def check_finite(res, label, check):
    import numpy as np
    vals = np.array([[r[k] for k in FLOAT_KEYS] for r in res.rows], float)
    check(f"{label}.finite_positive", bool(np.isfinite(vals).all()
                                            and (vals > 0).all()),
          f"{len(res.rows)} lanes")


def check_native_scan(spec, seeds, label, check):
    """The engine resolves the kernel path by itself, and the program it
    dispatches holds Mosaic custom calls: no kernel was interpreted."""
    from repro import sharding_ctx
    from repro.core.sweep import _prepare
    from repro.core.sweep_jax import JaxSweepEngine
    eng = JaxSweepEngine([_prepare(spec, s)[1] for s in seeds])
    check(f"{label}.use_pallas", eng.use_pallas is True)
    check(f"{label}.interpret_policy",
          sharding_ctx.default_interpret(None) is False)
    n = eng.lower().compile().as_text().count("tpu_custom_call")
    check(f"{label}.tpu_custom_call", n > 0, f"{n} in the compiled scan")


def check_kernels_exact(lanes, check, G=10, W=16, P=3, seed=0):
    """Each tick kernel against its oracle on the chip at the paper's
    shapes (G groups, W progress steps), with allocator totals in the
    thousands."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    R = lanes * G
    cells = jax.random.randint(ks[0], (R, W + 2), 0, 400, jnp.int32)
    k_pre = jax.random.randint(ks[1], (R,), 0, 8000, jnp.int32)
    idle = jax.random.randint(ks[2], (lanes, G), 0, 400, jnp.int32)
    k_m = jax.random.randint(ks[3], (lanes,), 0, 4000, jnp.int32)
    busy = jax.random.randint(ks[4], (R, W), 0, 400, jnp.int32)
    fin = jnp.arange(W)[None, :] >= jax.random.randint(ks[5], (R, 1), 1, W)
    rate = jax.random.uniform(ks[6], (lanes, G), minval=0.1, maxval=5.0)
    onehot = jax.nn.one_hot(jax.random.randint(ks[7], (G,), 0, P), P)

    def same(name, got, want):
        got, want = np.asarray(got), np.asarray(want)
        bad = int((got != want).sum())
        check(f"c.kernel.{name}", bad == 0, f"{bad} cells differ")

    same("preempt", ops.campaign_preempt(cells, k_pre),
         ref.campaign_preempt_ref(cells, k_pre))
    same("match", ops.campaign_match(idle, k_m),
         ref.campaign_match_ref(idle, k_m))
    adv, nfin = ops.campaign_advance(busy, fin)
    adv_r, nfin_r = ref.campaign_advance_ref(busy, fin)
    same("advance", adv, adv_r)
    same("advance.finished", nfin, nfin_r)
    spent, by_prov = ops.campaign_bill(idle, rate, onehot)
    spent_r, by_prov_r = ref.campaign_bill_ref(idle, rate, onehot)
    for name, got, want in (("spent", spent, spent_r),
                            ("by_provider", by_prov, by_prov_r),
                            ("by_provider_sum", by_prov.sum(-1), spent)):
        err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))
                           / np.maximum(np.abs(np.asarray(want)), 1e-6)))
        check(f"c.kernel.bill.{name}", err <= 1e-6, f"max rel err {err:.2e}")


def cap_times(events):
    return [e["t"] for e in events if e.get("event") == "budget_floor"]


def check_against_oracles(spec, seeds, rows, check):
    """Phase (c) per lane: the lanes of (a) with the jnp oracles in
    place of the Pallas kernels, on the same chip."""
    from repro.core.sweep_jax import run_jax_detailed
    t0 = time.perf_counter()
    oracle = run_jax_detailed([(spec, s) for s in seeds], use_pallas=False)
    print(f"phase c oracle run: lanes={len(seeds)} "
          f"wall_s={time.perf_counter() - t0:.3f}", flush=True)
    differ, cap_shift = [], []
    dt = spec.dt_h
    for row, (res, events, _tr) in zip(rows, oracle):
        same = all(row[k] == res[k] for k in COUNT_KEYS) and all(
            abs(row[k] - res[k]) <= REL_TOL * max(abs(res[k]), 1e-9)
            for k in FLOAT_KEYS)
        if same:
            continue
        ta, tb = cap_times(row["events_fired"]), cap_times(events)
        if len(ta) == len(tb) == 1 \
                and abs(abs(ta[0] - tb[0]) - dt) < 1e-9:
            # float summation order moved the budget-floor cap one tick
            cap_shift.append((row["seed"], ta[0], tb[0]))
        else:
            differ.append((row["seed"], {k: (row[k], res[k])
                                         for k in COUNT_KEYS + FLOAT_KEYS}))
    for seed, ta, tb in cap_shift:
        print(f"  cap-shift lane seed={seed}: budget floor at t={ta}h "
              f"(kernels) vs t={tb}h (oracles)")
    for seed, vals in differ[:10]:
        print(f"  differing lane seed={seed}: {vals}")
    check("c.lanes_equal", not differ,
          f"{len(seeds) - len(differ) - len(cap_shift)} equal, "
          f"{len(cap_shift)} cap-shift, {len(differ)} differ")


def check_host_bands(spec, jax_res, check, label):
    """Phase (d): the batched numpy engine over the first HOST_LANES
    seeds is the reference the jax bands must sit within."""
    from repro.core import api
    from repro.core.sweep_jax import STAT_BANDS, band_violations
    seeds = sorted(r["seed"] for r in jax_res.rows)[:HOST_LANES]
    t0 = time.perf_counter()
    ref = api.sweep([spec], seeds, engine="batched")
    print(f"phase d {label} numpy batched: lanes={len(seeds)} "
          f"wall_s={time.perf_counter() - t0:.3f}", flush=True)
    metrics = tuple(STAT_BANDS)
    rs, gs = ref.summary(metrics), jax_res.summary(metrics)
    for m in metrics:
        a, b = rs[spec.name][m], gs[spec.name][m]
        print(f"  {label} {m}: jax mean={b['mean']:.6g} "
              f"[{b['p5']:.6g}, {b['p95']:.6g}] numpy mean={a['mean']:.6g} "
              f"[{a['p5']:.6g}, {a['p95']:.6g}] band={STAT_BANDS[m]}")
    bad = band_violations(rs, gs)
    check(f"d.{label}.stat_bands", not bad,
          "; ".join(f"{m} {kind}" for _s, m, kind, _a, _b in bad))


def run(lanes: int = LANES, check=None) -> Checks:
    """Phases (a)-(d) on whatever backend is present (``main`` admits
    only a TPU)."""
    from repro.core import scenarios
    from repro.core.spec import CampaignSpec
    check = check or Checks()
    seeds = list(range(lanes))
    paper, burst = CampaignSpec(), scenarios.dataplane_burst()

    # each sweep first, so that its cold time includes the compile
    res_a = timed_sweep(paper, seeds, "a paper")
    check_finite(res_a, "a", check)
    check_native_scan(paper, seeds, "a", check)
    res_b = timed_sweep(burst, seeds, "b dataplane-burst")
    check_finite(res_b, "b", check)
    check_native_scan(burst, seeds, "b", check)

    check_kernels_exact(lanes, check)
    check_against_oracles(paper, seeds, res_a.rows, check)

    check_host_bands(paper, res_a, check, "paper")
    check_host_bands(burst, res_b, check, "dataplane-burst")
    return check


def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); this smoke runs only on the chip",
              file=sys.stderr)
        return 1
    from repro.compile_cache import use_compile_cache
    cache = use_compile_cache()
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__} "
          f"compile_cache={cache}", flush=True)
    t0 = time.perf_counter()
    checks = run()
    print(f"total_s={time.perf_counter() - t0:.3f}", flush=True)
    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} check(s) failed: "
              f"{', '.join(checks.failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
