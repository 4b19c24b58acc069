"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal, kv_len=None, scale=None,
                        q_offset=0):
    """q: (BHG, Sq, D); k/v: (BKV, Skv, D). Plain softmax attention."""
    BHG, Sq, D = q.shape
    BKV, Skv, _ = k.shape
    G = BHG // BKV
    scale = scale if scale is not None else D ** -0.5
    qg = q.reshape(BKV, G, Sq, D).astype(jnp.float32) * scale
    s = jnp.einsum("bgqd,bkd->bgqk", qg, k.astype(jnp.float32))
    kpos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if kv_len is not None:
        mask &= (kpos < kv_len)[None, :]
    if causal:
        qpos = q_offset + jnp.arange(Sq)
        mask &= qpos[:, None] >= kpos[None, :]
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgqk,bkd->bgqd", p, v.astype(jnp.float32))
    return o.reshape(BHG, Sq, D).astype(q.dtype)


def mamba_scan_ref(xc, dt, bm, cm, a):
    """Sequential selective scan. Shapes as mamba_scan_kernel."""
    B, S, di = xc.shape

    def step(h, inputs):
        xc_t, dt_t, b_t, c_t = inputs
        a_bar = jnp.exp(dt_t[:, :, None] * a[None])          # (B,di,N)
        h = a_bar * h + (dt_t * xc_t)[:, :, None] * b_t[:, None, :]
        y = (h * c_t[:, None, :]).sum(-1)                    # (B,di)
        return h, y

    h0 = jnp.zeros((B, di, a.shape[1]), jnp.float32)
    xs = (jnp.moveaxis(xc, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
          jnp.moveaxis(bm, 1, 0).astype(jnp.float32),
          jnp.moveaxis(cm, 1, 0).astype(jnp.float32))
    _, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(xc.dtype)           # (B,S,di)


def mlstm_ref(q, k, v, logi, logf):
    """Exact stabilized sequential mLSTM. q/k: (BH,S,dqk); v: (BH,S,dv);
    logi/logf: (BH,S,1). Returns (BH,S,dv)."""
    BH, S, dqk = q.shape
    dv = v.shape[2]
    kf = k.astype(jnp.float32) * (dqk ** -0.5)

    def step(carry, inputs):
        C, n, m = carry
        q_t, k_t, v_t, li_t, lf_t = inputs
        m1 = jnp.maximum(lf_t + m, li_t)                     # (BH,)
        fp = jnp.exp(lf_t + m - m1)
        ip = jnp.exp(li_t - m1)
        C = fp[:, None, None] * C + ip[:, None, None] * \
            jnp.einsum("bd,be->bde", k_t, v_t)
        n = fp[:, None] * n + ip[:, None] * k_t
        num = jnp.einsum("bd,bde->be", q_t, C)
        den = jnp.maximum(jnp.abs((n * q_t).sum(-1)), jnp.exp(-m1))
        return (C, n, m1), num / den[:, None]

    carry = (jnp.zeros((BH, dqk, dv), jnp.float32),
             jnp.zeros((BH, dqk), jnp.float32),
             jnp.zeros((BH,), jnp.float32))
    xs = (jnp.moveaxis(q.astype(jnp.float32), 1, 0),
          jnp.moveaxis(kf, 1, 0),
          jnp.moveaxis(v.astype(jnp.float32), 1, 0),
          jnp.moveaxis(logi[..., 0].astype(jnp.float32), 1, 0),
          jnp.moveaxis(logf[..., 0].astype(jnp.float32), 1, 0))
    _, hs = jax.lax.scan(step, carry, xs)
    return jnp.moveaxis(hs, 0, 1).astype(q.dtype)


def moe_gmm_ref(x, w):
    return jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(x.dtype)


# -- campaign-sweep tick ops (core/sweep_jax.py hot path) ------------------
# The jitted sweep engine tracks exchangeable instances as count planes
# (lane x group x progress-step), not per-instance rows; the tick ops are
# integer allocations and reductions over those planes.  The engine calls
# these jnp forms directly on CPU and swaps in the Pallas kernels
# (kernels/campaign_sweep.py) on TPU; test_kernels.py pins kernel == ref.

def campaign_alloc_scale(counts, k):
    """The allocator's per-row scale ``min(k, tot) / tot`` as (R,) f32
    (the Pallas wrapper feeds the kernel this same expression)."""
    tot = counts.sum(axis=-1)
    kk = jnp.minimum(k, tot)
    return kk.astype(jnp.float32) / jnp.maximum(tot, 1).astype(jnp.float32)


def campaign_alloc_ref(counts, k):
    """Proportional integer allocator: counts (R,C) i32 non-negative,
    k (R,) i32 -> take (R,C) i32 with 0 <= take <= counts and
    ``take.sum(-1) == min(k, counts.sum(-1))``.  Systematic (cumulative
    largest-remainder) rounding: exact, deterministic, one cumsum."""
    s = campaign_alloc_scale(counts, k)
    inc = jnp.cumsum(counts, axis=-1).astype(jnp.float32)
    exc = inc - counts.astype(jnp.float32)
    return (jnp.floor(inc * s[:, None] + 1e-3)
            - jnp.floor(exc * s[:, None] + 1e-3)).astype(jnp.int32)


def campaign_preempt_ref(counts, k):
    """Preemption fan-out: distribute each (lane, group)'s sampled
    preemption count ``k`` across its instance categories (idle,
    pilot-dead, busy-at-step-w) proportionally to occupancy.
    counts (R,C) i32, k (R,) i32 -> killed (R,C) i32."""
    return campaign_alloc_ref(counts, k)


def campaign_match_ref(idle, k):
    """Queue->pilot matcher core: split each lane's ``k`` matched jobs
    across groups proportionally to idle-pilot counts.
    idle (B,G) i32, k (B,) i32 -> take (B,G) i32."""
    return campaign_alloc_ref(idle, k)


def campaign_advance_ref(busy, fin_mask):
    """Pilot progress sync: busy (R,W) i32 job counts by progress step,
    fin_mask (R,W) bool (steps whose jobs complete after one more tick)
    -> (advanced (R,W) i32, finished (R,) i32).  Completing jobs leave;
    the rest shift one dt step right."""
    fin = busy * fin_mask.astype(busy.dtype)
    rest = busy - fin
    advanced = jnp.concatenate(
        [jnp.zeros_like(rest[:, :1]), rest[:, :-1]], axis=-1)
    return advanced, fin.sum(axis=-1)


def campaign_bill_ref(live, rate, prov_onehot):
    """Billing/ledger reduction: live (B,G) i32 instance counts,
    rate (B,G) f32 ($ owed per instance this interval), prov_onehot
    (G,P) -> (spent (B,) f32, by_provider (B,P) f32)."""
    amt = live.astype(jnp.float32) * rate
    return amt.sum(axis=-1), jnp.matmul(
        amt, prov_onehot, precision=jax.lax.Precision.HIGHEST)
