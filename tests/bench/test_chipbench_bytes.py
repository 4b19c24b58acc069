"""Byte counts of the tick and of each tick kernel, pinned to hand
counts at a small shape: B=2 lanes, G=3 groups, W=4 steps, L=2 levels,
P=2 providers, N=5 ticks; and the shapes the harness works out for the
``paper`` configuration, plain and with NAT-dropping groups."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

from benchmarks.chip import bench, cells, tickbytes  # noqa: E402

PLAIN = {"B": 2, "G": 3, "W": 4, "L": 2, "P": 2, "N": 5,
         "dp_gating": False, "dp_staging": False, "nat_any": False}
STAGED = dict(PLAIN, dp_gating=True, dp_staging=True)
NAT = dict(PLAIN, nat_any=True)
PAPER = cells.load_json("configs", "paper")
#: ``paper``'s bytes a tick by lanes a call, as counted before the
#: harness knew the NAT-drop program: G=10, W=16, L=5, P=3
PAPER_TICK_BYTES = {32: 57824, 1020: 1843140}


def nat_config(conf):
    """The configuration with OSG's default 5-minute keepalive, past
    Azure's 4-minute NAT idle timeout."""
    return dict(conf, spec=dict(conf["spec"], lease_interval_s=300.0))


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


def test_tick_bytes_nat():
    # the per-lane NAT mask, int32 [B, G], read each tick: 4*2*3 = 24;
    # the carry already holds the NAT-drop counter
    assert tickbytes.carry_bytes(NAT) == 302
    assert tickbytes.plane_bytes(NAT) == 114 + 4 * 2 * 3
    assert tickbytes.tick_bytes(NAT) - tickbytes.tick_bytes(PLAIN) == 24


@pytest.mark.parametrize("lanes", sorted(PAPER_TICK_BYTES))
def test_paper_tick_bytes_are_pinned(lanes):
    s = bench.cell_shape(PAPER, lanes)
    assert s == {"B": lanes, "G": 10, "W": 16, "L": 5, "P": 3, "N": 1344,
                 "dp_gating": False, "dp_staging": False, "nat_any": False}
    assert tickbytes.tick_bytes(s) == PAPER_TICK_BYTES[lanes]
    nat = bench.cell_shape(nat_config(PAPER), lanes)
    assert tickbytes.tick_bytes(nat) - tickbytes.tick_bytes(s) \
        == 4 * lanes * s["G"]


def test_cell_shape_takes_a_nat_configuration():
    """Azure's groups reach their NAT timeout; the shapes are the plain
    configuration's."""
    assert bench.cell_shape(nat_config(PAPER), 32) == \
        dict(bench.cell_shape(PAPER, 32), nat_any=True)


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
