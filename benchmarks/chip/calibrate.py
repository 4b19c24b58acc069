"""Readings that the limits in ``limits/<workload>.json`` are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --first-seed <n> --seeds 12 --control-seeds 3 --seconds <s>

One process sets the cell up once, then for each seed runs a window of
``--seconds`` and compares its drawn lanes with the float32 reference,
as a benchmark run does: the lower readings.  For the first
``--control-seeds`` seeds the control, the reference computed in
bfloat16 and put in the program's place, is compared with the float32
reference on the same lanes: the upper readings.  Prints one JSON line
per seed and writes them all to ``chiprun_out/calibrate/<cell>.json``.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def main(argv=None) -> int:
    import argparse

    import ml_dtypes

    from benchmarks.chip import correct, reference
    from benchmarks.chip.bench import Cell, NoChip, log
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=4.0)
    a = ap.parse_args(argv)
    cell = Cell(a.workload)
    try:
        cell.setup(T_START, a.first_seed)
    except NoChip as e:
        log(f"calibrate: {e}")
        return 3
    out = []
    for k in range(a.seeds):
        seed = a.first_seed + k
        cell.window(seed, a.seconds, traced=False)
        nums, drawn, want = cell.compare(seed)
        rec = {"seed": seed, "lanes": len(drawn), "calls": len(cell.done),
               "program": nums}
        if k < a.control_seeds:
            t = time.perf_counter()
            ctl = reference.reference_rows(
                [(s, sd) for s, sd, _r in drawn],
                cell.conf["catalog_tables"], ml_dtypes.bfloat16)
            rec["control"] = correct.numbers(ctl, want)
            rec["control_s"] = time.perf_counter() - t
        print(json.dumps(rec), flush=True)
        out.append(rec)
    dest = ROOT / "chiprun_out" / "calibrate"
    dest.mkdir(parents=True, exist_ok=True)
    (dest / f"{a.workload}.json").write_text(json.dumps(
        {"workload": a.workload, "device": cell.device, "runs": out},
        indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
