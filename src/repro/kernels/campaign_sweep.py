"""Pallas kernels for the jitted campaign-sweep engine's per-tick ops.

core/sweep_jax.py runs B campaigns as one ``lax.scan`` over ticks.  Its
state is *count planes*: instances within a (lane, group, progress-step)
cell are exchangeable, so the engine tracks how many sit in each cell
rather than per-instance rows.  The four ops here are its hot per-tick
phases over those planes:

  * ``campaign_preempt_kernel`` — preemption fan-out: distribute each
    (lane, group)'s sampled preemption count across its occupancy cells,
  * ``campaign_match_kernel``   — the queue->pilot matcher core: split a
    lane's matched-job count across groups by idle-pilot counts,
  * ``campaign_advance_kernel`` — pilot progress sync: completing jobs
    leave, the rest shift one dt step,
  * ``campaign_bill_kernel``    — the billing/ledger reduction.

Preempt and match share one body: a *systematic proportional integer
allocator* (cumulative largest-remainder rounding).  One inclusive
prefix sum, then ``floor(inclusive * k/tot) - floor(exclusive * k/tot)``
splits ``k`` units across cells proportionally, exactly and
deterministically.

TPU adaptation notes:
  * the grid tiles the row axis only (``block_r`` rows per program); a
    program sees each row's full cell axis, so every op is one VPU pass
    with no cross-program reductions.  A grid step has a fixed cost, so
    the ops.py wrappers size ``block_r`` from the rows
    (``ops.tick_row_block``): the fewest equal blocks of at most
    ``ops.TICK_BLOCK_ROWS`` rows (a VMEM budget), each a multiple of 8,
  * counts travel as int32 (Pallas TPU has no first-class bool tiles)
    and the allocator's scale factor rides in f32 — cumulative counts
    stay far below 2**24, so the f32 floors are exact,
  * the prefix sum is a log-step scan over the cell (lane) axis:
    ``pltpu.roll`` (``jnp.roll`` direction) plus an iota mask, in int32
    — Mosaic has no ``cumsum`` lowering,
  * the scale ``min(k, tot) / tot`` arrives precomputed: the ops.py
    wrapper computes it with the oracle's own XLA expression, so
    kernel == oracle does not depend on Mosaic and XLA rounding an f32
    division alike,
  * the advance shift avoids gathers: ``lax.roll`` + an iota mask on
    the step axis,
  * like flash_attention, CPU/CI runs use ``interpret=True`` via the
    ops.py wrappers (sharding_ctx.default_interpret).

The jnp oracles live in kernels/ref.py; tests/test_kernels.py pins
kernel == ref exactly (integer ops throughout, so the comparison is
equality, not allclose).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _alloc_body(c_ref, s_ref, o_ref):
    counts = c_ref[...]                                # (br, C) i32
    lane = jax.lax.broadcasted_iota(jnp.int32, counts.shape, 1)
    inc = counts
    d = 1
    while d < counts.shape[1]:          # inclusive scan, log2(C) rounds
        inc = inc + jnp.where(lane >= d, pltpu.roll(inc, d, 1), 0)
        d *= 2
    s = s_ref[...]                                     # (br, 1) f32
    inc_f = inc.astype(jnp.float32)
    exc_f = inc_f - counts.astype(jnp.float32)
    o_ref[...] = (jnp.floor(inc_f * s + 1e-3)
                  - jnp.floor(exc_f * s + 1e-3)).astype(jnp.int32)


def _alloc_call(counts, scale, *, block_r, interpret):
    R, C = counts.shape
    spec = pl.BlockSpec((block_r, C), lambda i: (i, 0))
    return pl.pallas_call(
        _alloc_body,
        grid=(R // block_r,),
        in_specs=[spec, pl.BlockSpec((block_r, 1), lambda i: (i, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.int32),
        interpret=interpret)(counts, scale)


def campaign_preempt_kernel(counts, scale, *, block_r, interpret=False):
    """counts (R,C) i32 occupancy cells per (lane, group) row, scale
    (R,1) f32 ``min(k, total) / total`` for the sampled preemption
    counts k -> killed (R,C) i32 (proportional systematic split,
    killed <= counts, rows sum to min(k, total))."""
    return _alloc_call(counts, scale, block_r=block_r, interpret=interpret)


def campaign_match_kernel(idle, scale, *, block_r, interpret=False):
    """idle (B,G) i32 idle-pilot counts, scale (B,1) f32 for the matched
    jobs per lane -> take (B,G) i32 (same allocator over lane rows)."""
    return _alloc_call(idle, scale, block_r=block_r, interpret=interpret)


def _advance_body(b_ref, f_ref, a_ref, n_ref):
    busy = b_ref[...]                                  # (br, W) i32
    fin = busy * f_ref[...]
    rest = busy - fin
    # shift one dt step right, gather-free: roll + mask the rolled-in
    # column with an iota test
    w = jax.lax.broadcasted_iota(jnp.int32, busy.shape, busy.ndim - 1)
    a_ref[...] = jnp.where(w == 0, 0, jnp.roll(rest, 1, axis=-1))
    n_ref[...] = fin.sum(axis=-1, keepdims=True)


def campaign_advance_kernel(busy, fin_mask, *, block_r, interpret=False):
    """busy (R,W) i32 job counts by progress step, fin_mask (R,W) i32
    (1 where one more tick completes the job) -> (advanced (R,W) i32,
    finished (R,1) i32)."""
    R, W = busy.shape
    spec = pl.BlockSpec((block_r, W), lambda i: (i, 0))
    return pl.pallas_call(
        _advance_body,
        grid=(R // block_r,),
        in_specs=[spec, spec],
        out_specs=(spec, pl.BlockSpec((block_r, 1), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((R, W), jnp.int32),
                   jax.ShapeDtypeStruct((R, 1), jnp.int32)),
        interpret=interpret)(busy, fin_mask)


def _bill_body(l_ref, r_ref, p_ref, s_ref, o_ref):
    amt = l_ref[...].astype(jnp.float32) * r_ref[...]  # (br, G)
    s_ref[...] = amt.sum(axis=-1, keepdims=True)
    # HIGHEST: a default-precision f32 matmul on the MXU rounds amt to
    # bf16, and by-provider totals would drift from spent
    o_ref[...] = jax.lax.dot_general(
        amt, p_ref[...], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def campaign_bill_kernel(live, rate, prov_onehot, *, block_r,
                         interpret=False):
    """live (B,G) i32 instance counts, rate (B,G) f32 $/instance this
    interval, prov_onehot (G,P) f32 -> (spent (B,1) f32,
    by_provider (B,P) f32)."""
    B, G = live.shape
    P = prov_onehot.shape[1]
    spec = pl.BlockSpec((block_r, G), lambda i: (i, 0))
    return pl.pallas_call(
        _bill_body,
        grid=(B // block_r,),
        in_specs=[spec, spec, pl.BlockSpec((G, P), lambda i: (0, 0))],
        out_specs=(pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
                   pl.BlockSpec((block_r, P), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((B, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, P), jnp.float32)),
        interpret=interpret)(live, rate, prov_onehot)
