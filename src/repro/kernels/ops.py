"""jit'd public wrappers around the Pallas kernels: model-layout adapters,
MXU-alignment padding, and the interpret-mode policy
(``sharding_ctx.default_interpret``: native on TPU, interpreted elsewhere).

``flash_attention`` plugs into models/attention.py via the flash_fn hook
(RunConfig.attention_impl == "pallas"); the others are drop-in replacements
for the reference einsums/scans at the same call sites.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.campaign_sweep import (campaign_advance_kernel,
                                          campaign_bill_kernel,
                                          campaign_match_kernel,
                                          campaign_preempt_kernel)
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.mamba_scan import mamba_scan_kernel
from repro.kernels.mlstm_chunk import mlstm_chunk_kernel
from repro.kernels.moe_gmm import moe_gmm_kernel
from repro.kernels.ref import campaign_alloc_scale
from repro.sharding_ctx import default_interpret


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128,
                    interpret=None):
    """Model layout: q (B,Sq,H,D), k/v (B,Skv,Hkv,D) -> (B,Sq,H,D)."""
    interpret = default_interpret(interpret)
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    scale = D ** -0.5
    qk = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kk = k.transpose(0, 2, 1, 3).reshape(B * Hkv, -1, D)
    vv = v.transpose(0, 2, 1, 3).reshape(B * Hkv, -1, D)
    Skv = kk.shape[1]
    qk, _ = _pad_to(qk, 2, 128)
    kk, _ = _pad_to(kk, 2, 128)
    vv, _ = _pad_to(vv, 2, 128)
    bq = min(block_q, max(8, Sq))
    bk = min(block_k, max(8, Skv))
    qk, pq = _pad_to(qk, 1, bq)
    kk, _ = _pad_to(kk, 1, bk)
    vv, _ = _pad_to(vv, 1, bk)
    o = flash_attention_kernel(qk, kk, vv, causal=causal, kv_len=Skv,
                               scale=scale, block_q=bq, block_k=bk,
                               interpret=interpret)
    o = o[:, :Sq, :D].reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    return o


@functools.partial(jax.jit, static_argnames=("block_d", "block_s",
                                             "interpret"))
def mamba_scan(xc, dt, bm, cm, a, *, block_d=128, block_s=64,
               interpret=None):
    """xc/dt: (B,S,di); bm/cm: (B,S,N); a: (di,N) -> y (B,S,di)."""
    interpret = default_interpret(interpret)
    B, S, di = xc.shape
    bd = min(block_d, di)
    bs = min(block_s, S)
    if di % bd or S % bs:
        xc, _ = _pad_to(xc, 2, bd)
        dt, _ = _pad_to(dt, 2, bd)
        a, _ = _pad_to(a, 0, bd)
        xc, _ = _pad_to(xc, 1, bs)
        dt, _ = _pad_to(dt, 1, bs)
        bm, _ = _pad_to(bm, 1, bs)
        cm, _ = _pad_to(cm, 1, bs)
    y = mamba_scan_kernel(xc, dt, bm, cm, a, block_d=bd, block_s=bs,
                          interpret=interpret)
    return y[:, :S, :di]


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def mlstm_chunk(q, k, v, logi, logf, *, block_s=128, interpret=None):
    """q/k: (BH,S,dqk); v: (BH,S,dv); gates (BH,S,1) -> h (BH,S,dv)."""
    interpret = default_interpret(interpret)
    S = q.shape[1]
    bs = min(block_s, S)
    assert S % bs == 0, "pad sequence to a chunk multiple upstream"
    return mlstm_chunk_kernel(q, k, v, logi, logf, block_s=bs,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "block_k",
                                             "interpret"))
def moe_gmm(x, w, *, block_c=128, block_f=128, block_k=128, interpret=None):
    """x: (E,C,D) @ w: (E,D,F) -> (E,C,F), fp32 accumulation."""
    interpret = default_interpret(interpret)
    E, C, D = x.shape
    F = w.shape[2]
    bc, bf, bk = min(block_c, C), min(block_f, F), min(block_k, D)
    xp, _ = _pad_to(_pad_to(x, 1, bc)[0], 2, bk)
    wp, _ = _pad_to(_pad_to(w, 1, bk)[0], 2, bf)
    o = moe_gmm_kernel(xp, wp, block_c=bc, block_f=bf, block_k=bk,
                       interpret=interpret)
    return o[:, :C, :F]


# -- campaign-sweep tick ops (core/sweep_jax.py) ---------------------------
# Same contract as the model kernels above: the wrapper owns layout
# padding (cell axis to a VPU lane multiple, row axis to the row block)
# and the interpret-mode policy; kernels/ref.py holds the jnp oracles
# (and the allocator scale both sides share).
#
# A grid step costs a fixed ~0.5 us on a v5e whatever its rows, so each
# wrapper takes the fewest row blocks that fit a VMEM budget.  A block
# row costs 128 lanes x 4 bytes in every (block, *) plane, the (block, 1)
# columns included, and 16 such planes bound every tick kernel: at most
# 4 operand blocks, double-buffered, plus the body's temporaries (the
# allocator's iota, roll, inc, inc_f and exc_f).
TICK_VMEM_BUDGET = 8 << 20           # half of v5e's 16 MiB scoped VMEM
TICK_ROW_BYTES = 16 * 128 * 4
TICK_BLOCK_ROWS = TICK_VMEM_BUDGET // TICK_ROW_BYTES        # 1,024


def tick_row_block(rows, cap=TICK_BLOCK_ROWS):
    """Row block for ``rows`` rows: the fewest equal blocks of at most
    ``cap`` rows, each rounded up to a multiple of 8 sublanes, so the
    rows pad to ``ceil(rows / block)`` blocks with under 8 rows of
    padding a block."""
    n = -(-rows // cap)
    return (-(-rows // n) + 7) // 8 * 8


def _pad2(x, block_r, c_mult=128):
    x, _ = _pad_to(x, 0, block_r)
    x, _ = _pad_to(x, 1, c_mult)
    return x


@functools.partial(jax.jit, static_argnames=("interpret",))
def campaign_preempt(counts, k, *, interpret=None):
    """Preemption fan-out: counts (R,C) i32 occupancy cells per
    (lane, group) row, k (R,) i32 sampled preemption counts ->
    killed (R,C) i32 (proportional systematic split)."""
    interpret = default_interpret(interpret)
    R, C = counts.shape
    br = tick_row_block(R)
    counts = counts.astype(jnp.int32)
    sp = _pad_to(campaign_alloc_scale(counts, k)[:, None], 0, br)[0]
    killed = campaign_preempt_kernel(_pad2(counts, br), sp,
                                     block_r=br, interpret=interpret)
    return killed[:R, :C]


@functools.partial(jax.jit, static_argnames=("interpret",))
def campaign_match(idle, k, *, interpret=None):
    """Queue->pilot matcher core: idle (B,G) i32 idle-pilot counts,
    k (B,) i32 matched jobs per lane -> take (B,G) i32."""
    interpret = default_interpret(interpret)
    B, G = idle.shape
    br = tick_row_block(B)
    idle = idle.astype(jnp.int32)
    sp = _pad_to(campaign_alloc_scale(idle, k)[:, None], 0, br)[0]
    take = campaign_match_kernel(_pad2(idle, br), sp,
                                 block_r=br, interpret=interpret)
    return take[:B, :G]


@functools.partial(jax.jit, static_argnames=("interpret",))
def campaign_advance(busy, fin_mask, *, interpret=None):
    """Pilot progress sync: busy (R,W) i32 job counts by progress step,
    fin_mask (R,W) bool -> (advanced (R,W) i32, finished (R,) i32)."""
    interpret = default_interpret(interpret)
    R, W = busy.shape
    br = tick_row_block(R)
    adv, fin = campaign_advance_kernel(
        _pad2(busy.astype(jnp.int32), br),
        _pad2(fin_mask.astype(jnp.int32), br),
        block_r=br, interpret=interpret)
    return adv[:R, :W], fin[:R, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def campaign_bill(live, rate, prov_onehot, *, interpret=None):
    """Billing/ledger reduction: live (B,G) i32 instance counts,
    rate (B,G) f32, prov_onehot (G,P) f32 -> (spent (B,) f32,
    by_provider (B,P) f32)."""
    interpret = default_interpret(interpret)
    B, G = live.shape
    P = prov_onehot.shape[1]
    br = tick_row_block(B)
    oh = _pad_to(_pad_to(prov_onehot.astype(jnp.float32), 0, 128)[0],
                 1, 128)[0]
    spent, by_prov = campaign_bill_kernel(
        _pad2(live.astype(jnp.int32), br),
        _pad2(rate.astype(jnp.float32), br), oh,
        block_r=br, interpret=interpret)
    return spent[:B, 0], by_prov[:B, :P]
