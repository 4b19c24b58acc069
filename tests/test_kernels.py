"""Per-kernel shape/dtype sweeps vs the ref.py pure-jnp oracles
(interpret=True on CPU; assignment requirement)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,Skv,H,Hkv,D,causal", [
    (1, 128, 128, 2, 2, 64, True),
    (2, 256, 256, 4, 2, 64, True),      # GQA
    (1, 128, 384, 2, 1, 128, False),    # cross-ish, MQA
    (2, 96, 160, 2, 2, 80, True),       #非-128-aligned (padding path)
])
def test_flash_attention(B, Sq, Skv, H, Hkv, D, causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D)).astype(dtype)
    o = ops.flash_attention(q, k, v, causal=causal)
    G = H // Hkv
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    orf = ref.flash_attention_ref(qr, kr, vr, causal=causal)
    orf = orf.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(orf, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,di,N,bd,bs", [
    (1, 64, 32, 8, 32, 32),
    (2, 128, 64, 16, 32, 64),
    (1, 96, 48, 8, 16, 32),             # padding path
])
def test_mamba_scan(B, S, di, N, bd, bs, dtype):
    ks = jax.random.split(KEY, 5)
    xc = jax.random.normal(ks[0], (B, S, di)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di))).astype(dtype)
    bm = jax.random.normal(ks[2], (B, S, N)).astype(dtype)
    cm = jax.random.normal(ks[3], (B, S, N)).astype(dtype)
    a = -jnp.exp(jax.random.normal(ks[4], (di, N)))
    y = ops.mamba_scan(xc, dt, bm, cm, a, block_d=bd, block_s=bs)
    yr = ref.mamba_scan_ref(xc, dt, bm, cm, a)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("BH,S,dqk,dv,bs", [
    (2, 128, 32, 32, 64),
    (4, 256, 64, 64, 128),
    (1, 128, 16, 48, 32),               # dqk != dv
])
def test_mlstm_chunk(BH, S, dqk, dv, bs, dtype):
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (BH, S, dqk)).astype(dtype)
    k = jax.random.normal(ks[1], (BH, S, dqk)).astype(dtype)
    v = jax.random.normal(ks[2], (BH, S, dv)).astype(dtype)
    li = (jax.random.normal(ks[3], (BH, S, 1)) - 5.0).astype(dtype)
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (BH, S, 1))
                            + 3.0).astype(dtype)
    h = ops.mlstm_chunk(q, k, v, li, lf, block_s=bs)
    hr = ref.mlstm_ref(q, k, v, li, lf)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(hr, np.float32), **tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,D,F", [
    (2, 64, 32, 64),
    (4, 128, 64, 96),
    (3, 72, 40, 56),                    # all-unaligned (padding path)
])
def test_moe_gmm(E, C, D, F, dtype):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (E, C, D)).astype(dtype)
    w = jax.random.normal(ks[1], (E, D, F)).astype(dtype)
    o = ops.moe_gmm(x, w, block_c=32, block_f=32, block_k=16)
    orf = ref.moe_gmm_ref(x, w)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(orf, np.float32), **_tol(dtype))


def test_flash_matches_model_attention():
    """The kernel agrees with the model's chunked reference attention."""
    from repro.models.attention import chunked_attention
    B, S, H, D = 2, 128, 4, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    o_kernel = ops.flash_attention(q, k, v, causal=True)
    pos = jnp.arange(S)
    o_model = chunked_attention(q, k, v, q_positions=pos, kv_positions=pos,
                                causal=True, q_chunk=64)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_model),
                               rtol=2e-5, atol=2e-5)


# -- campaign-sweep tick ops (core/sweep_jax.py) ---------------------------
# Integer semantics, so the wrappers (Pallas, interpret=True on CPU)
# must match the ref.py oracles *exactly* — these are the per-tick ops
# the jitted engine dispatches through kernels when on TPU.

def _counts(key, shape, hi=30):
    return jax.random.randint(key, shape, 0, hi, dtype=jnp.int32)


def _tick(fn, cap, monkeypatch):
    """The wrapper as the engine calls it, or, given ``cap``, run afresh
    with its row block chosen under that cap (several blocks, the last
    one ragged, at a test's small R)."""
    if cap is None:
        return fn
    monkeypatch.setattr(ops, "tick_row_block",
                        functools.partial(ops.tick_row_block, cap=cap))
    return fn.__wrapped__


# the row-block rule's edges: one row, R not a multiple of 8, three
# blocks under a cap of 16 (37 rows pad to 48), and the paper's shapes
# at B = 32 (G = 10, W = 16)
@pytest.mark.parametrize("R,C,cap", [
    (8, 5, None), (20, 10, None), (3, 16, None), (64, 7, None),
    (1, 18, None), (13, 18, None), (37, 18, 16), (320, 18, None)])
def test_campaign_preempt(R, C, cap, monkeypatch):
    ks = jax.random.split(KEY, 2)
    counts = _counts(ks[0], (R, C))
    tot = counts.sum(-1)
    # k spans the edge cases: 0, everything, and beyond-everything
    # (the allocator must clip; rows keep counts >= 0)
    k = jnp.concatenate([jnp.zeros(1, jnp.int32), tot[1:2],
                         tot[2:3] + 7,
                         jax.random.randint(ks[1], (R - 3,), 0, 40)
                         .astype(jnp.int32)]) if R >= 3 else tot
    killed = _tick(ops.campaign_preempt, cap, monkeypatch)(
        counts, k, interpret=True)
    killed_ref = ref.campaign_preempt_ref(counts, k)
    np.testing.assert_array_equal(np.asarray(killed),
                                  np.asarray(killed_ref))
    kil = np.asarray(killed)
    cnt = np.asarray(counts)
    assert (kil >= 0).all() and (kil <= cnt).all()
    np.testing.assert_array_equal(
        kil.sum(-1), np.minimum(np.asarray(k), cnt.sum(-1)))


@pytest.mark.parametrize("B,G,cap", [
    (4, 3, None), (16, 10, None), (9, 12, None),
    (1, 10, None), (13, 10, None), (37, 10, 16), (32, 10, None)])
def test_campaign_match(B, G, cap, monkeypatch):
    ks = jax.random.split(KEY, 2)
    idle = _counts(ks[0], (B, G))
    k = jax.random.randint(ks[1], (B,), 0, 60).astype(jnp.int32)
    take = _tick(ops.campaign_match, cap, monkeypatch)(idle, k,
                                                        interpret=True)
    take_ref = ref.campaign_match_ref(idle, k)
    np.testing.assert_array_equal(np.asarray(take), np.asarray(take_ref))


@pytest.mark.parametrize("R,W,cap", [
    (8, 16, None), (20, 16, None), (5, 9, None),
    (1, 16, None), (13, 16, None), (37, 16, 16), (320, 16, None)])
def test_campaign_advance(R, W, cap, monkeypatch):
    ks = jax.random.split(KEY, 2)
    busy = _counts(ks[0], (R, W))
    wfin1 = jax.random.randint(ks[1], (R, 1), 1, W)
    fin_mask = jnp.arange(W)[None, :] >= wfin1     # suffix, like finmask
    adv, fin = _tick(ops.campaign_advance, cap, monkeypatch)(
        busy, fin_mask, interpret=True)
    adv_ref, fin_ref = ref.campaign_advance_ref(busy, fin_mask)
    np.testing.assert_array_equal(np.asarray(adv), np.asarray(adv_ref))
    np.testing.assert_array_equal(np.asarray(fin), np.asarray(fin_ref))
    # conservation: finished + surviving == starting population, minus
    # whatever sat unfinished at w = W-1 (the engine sizes W so that
    # column is always finished; here we account for it explicitly)
    lost = np.where(np.asarray(fin_mask)[:, -1], 0,
                    np.asarray(busy)[:, -1])
    np.testing.assert_array_equal(
        np.asarray(fin) + np.asarray(adv).sum(-1) + lost,
        np.asarray(busy).sum(-1))


@pytest.mark.parametrize("B,G,P,cap", [
    (4, 3, 2, None), (16, 10, 3, None), (7, 12, 5, None),
    (1, 10, 3, None), (13, 10, 3, None), (37, 10, 3, 16),
    (32, 10, 3, None)])
def test_campaign_bill(B, G, P, cap, monkeypatch):
    ks = jax.random.split(KEY, 3)
    live = _counts(ks[0], (B, G))
    rate = jax.random.uniform(ks[1], (B, G), minval=0.1, maxval=5.0)
    prov = jax.random.randint(ks[2], (G,), 0, P)
    onehot = jax.nn.one_hot(prov, P, dtype=jnp.float32)
    spent, by_prov = _tick(ops.campaign_bill, cap, monkeypatch)(
        live, rate, onehot, interpret=True)
    spent_ref, by_prov_ref = ref.campaign_bill_ref(live, rate, onehot)
    np.testing.assert_allclose(np.asarray(spent), np.asarray(spent_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(by_prov),
                               np.asarray(by_prov_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(by_prov).sum(-1),
                               np.asarray(spent), rtol=1e-6, atol=1e-6)


# rows -> grid steps: the match and bill rows at B = 32 and 1020, the
# preempt and advance rows there (G = 10: 320, 10,200) and at B = 4080,
# the edges around the cap, and a small cap whose last block is ragged
@pytest.mark.parametrize("R,cap,steps", [
    (1, None, 1), (7, None, 1), (32, None, 1), (320, None, 1),
    (1020, None, 1), (1024, None, 1), (1025, None, 2), (10200, None, 10),
    (40800, None, 40), (37, 16, 3)])
def test_tick_row_block(R, cap, steps):
    cap = cap or ops.TICK_BLOCK_ROWS
    block = ops.tick_row_block(R, cap)
    n = -(-R // block)
    assert n == steps == -(-R // cap)      # the fewest blocks under cap
    assert block % 8 == 0
    assert R <= n * block < R + 8 * n      # under 8 pad rows a step
    assert block * ops.TICK_ROW_BYTES <= ops.TICK_VMEM_BUDGET
