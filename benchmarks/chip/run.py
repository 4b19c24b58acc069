"""Run one benchmark cell once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

(``python3 -m benchmarks.chip.run ...`` from the repository root does
the same.)  The cell, its configuration, traffic mix, metrics and
limits are found by name from ``BENCHMARK.json``.  It runs only on a
TPU: elsewhere, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  JAX's compilation cache is kept in
``.jax_cache`` at the root of the checkout.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
# the cache the program's use_compile_cache() takes, inside the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

if __name__ == "__main__":
    from benchmarks.chip.bench import main
    sys.exit(main(t_start=T_START))
