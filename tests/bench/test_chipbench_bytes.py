"""Byte counts of the tick and of each tick kernel, pinned to hand
counts at a small shape: B=2 lanes, G=3 groups, W=4 steps, L=2 levels,
P=2 providers, N=5 ticks."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import cells, tickbytes  # noqa: E402

PLAIN = {"B": 2, "G": 3, "W": 4, "L": 2, "P": 2, "N": 5,
         "dp_gating": False, "dp_staging": False}
STAGED = dict(PLAIN, dp_gating=True, dp_staging=True)


def test_tick_bytes_plain():
    # carry: 4 B x (3*B*G=18 + B*G*W=24 + B*L=4 + 9*B=18 + 2*B*P=8) = 288
    # plus 1 B x (5*B + 2*B) = 14 booleans
    assert tickbytes.carry_bytes(PLAIN) == 302
    # planes: rate, cap 4*2*6=48; outage 2; five lane ints 40; budget,
    # overhead, finish step 24
    assert tickbytes.plane_bytes(PLAIN) == 114
    assert tickbytes.tick_bytes(PLAIN) == 2 * 302 + 114
    assert tickbytes.call_bytes(PLAIN) == 5 * 718


def test_tick_bytes_staged():
    # + 4 B x (3*B*G + 3*B) = 96 of staging carry
    assert tickbytes.carry_bytes(STAGED) == 302 + 96
    # + origin up 6, miss ticks 24, flush edge 6
    assert tickbytes.plane_bytes(STAGED) == 114 + 36
    assert tickbytes.tick_bytes(STAGED) == 2 * 398 + 150


def test_kernel_bytes():
    k = {m.__name__.rsplit("_", 1)[-1]: m for m in cells.kernel_modules()}
    assert set(k) == {"preempt", "match", "advance", "bill"}
    # preempt: R=6 rows x C=6 cells, counts and killed 2*36 + scale 6,
    # x4 B = 312, twice a tick
    assert k["preempt"].bytes_per_tick(PLAIN) == 2 * 312
    # match: idle and take 2*6 + scale 2, x4
    assert k["match"].bytes_per_tick(PLAIN) == 56
    # busy, finish mask, advanced 3*6*4 + finished 6, x4
    assert k["advance"].bytes_per_tick(PLAIN) == 312
    # live, rate 2*6; map 3*2; spent 2; by provider 4; x4
    assert k["bill"].bytes_per_tick(PLAIN) == 96
    assert all(m.EVENT_NAMES for m in k.values())
