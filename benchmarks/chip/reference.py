"""Plain reference of what ``api.sweep(specs, seeds, engine="jax")``
returns for one lane: the count-plane campaign tick, stepped tick by
tick in numpy from the spec's JSON and the provider tables in the
configuration file.

It imports nothing of the program and takes nothing that the program
made.  The semantics are those the compiled engine states for itself
(its statistical tier): exchangeable instances as counts per
(lane, group, progress step), a Poisson preemption total per
(lane, group) and tick drawn from threefry uniforms keyed by
(seed, tick), split over occupancy cells by systematic proportional
rounding, the queue->pilot matcher split the same way, jobs advancing
one dt step per tick, billing of the live set at the tick's start, the
budget-floor tripwire applied at the next tick, NAT drops (every busy
pilot in a group whose NAT idle timeout the lease interval reaches loses
its job to the queue after the match, and its instance stays alive and
billed with a dead pilot), and the data plane's stage-in as a front
extension of the progress axis.  Where the engine bakes per-segment
planes, this steps each lane's own events at their fire ticks; the two
are the same campaign.

``dtype`` is the precision of every float the tick computes: float32,
the engine's stated precision, or a lower one for the control.  Counts
are exact integers throughout.  Uniforms come from ``jax.random`` (the
library's documented threefry scheme, not the program).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: ledger alert fractions, highest first (the budget ledger's defaults)
THRESHOLDS = (0.5, 0.25, 0.2, 0.1, 0.05)
POISSON_TERMS = 24      # inverse-CDF terms below the normal switch
POISSON_LAM_CLIP = 30.0
POISSON_NORMAL_ABOVE = 8.0
ALLOC_EPS = 1e-3        # floor guard of the proportional allocator
SUPPORTED_EVENTS = {"set_target", "ce_outage", "origin_outage",
                    "origin_degrade", "cache_flush"}


# -- lane set-up from the spec's JSON ---------------------------------------

def _groups(spec: dict, tables: dict) -> List[dict]:
    """The (provider, region) groups in the engine's order: by price,
    then provider name, then region name."""
    if spec.get("providers") is not None:
        raise ValueError("reference: inline providers not supported")
    for key, ok in (("gpu_slicing", None), ("ondemand_fraction", 0.0)):
        if spec.get(key, ok) != ok:
            raise ValueError(f"reference: {key} not supported")
    price_key = "spot_price_per_day" if spec["spot"] \
        else "ondemand_price_per_day"
    out = []
    for p in tables[spec["catalog"]]:
        price = p[price_key]
        if spec["price_scale"] != 1.0:
            price = price * spec["price_scale"]
        for r in p["regions"]:
            cap = r["capacity"]
            if spec["capacity_scale"] != 1.0:
                cap = max(1, int(cap * spec["capacity_scale"]))
            nat = p.get("nat_idle_timeout_s")
            out.append({"provider": p["name"], "region": r["name"],
                        "price": price, "cap": cap,
                        "pre_rate": r["preempt_rate_per_hour"],
                        "pre_scale": r.get("preempt_scale_at_full", 3.0),
                        "nat": float("inf") if nat is None else nat})
    out.sort(key=lambda g: (g["price"], g["provider"], g["region"]))
    return out


def _compile_timeline(timeline: Sequence[dict]) -> List[tuple]:
    """Events as time-sorted ``(t, op, arg)``, stable by position."""
    ops = []
    for ev in timeline:
        kind = ev["kind"]
        if kind not in SUPPORTED_EVENTS:
            raise ValueError(f"reference: event {kind!r} not supported")
        at = ev["at_h"]
        if kind == "set_target":
            ops.append((at, "scale", ev["target"]))
        elif kind == "ce_outage":
            ops += [(at, "outage_on", 0),
                    (at + ev["duration_h"], "outage_off",
                     ev["resume_target"])]
        elif kind == "origin_outage":
            ops += [(at, "origin_on", ev["provider"]),
                    (at + ev["duration_h"], "origin_off", ev["provider"])]
        elif kind == "origin_degrade":
            ops.append((at, "origin_degrade",
                        (ev["provider"], ev["factor"])))
        else:
            ops.append((at, "cache_flush", ev["provider"]))
    ops.sort(key=lambda e: e[0])
    return ops


def _stage_ticks(size_gb, gbps, dt):
    gbps = np.asarray(gbps, np.float64)
    hours = size_gb * 8.0 / np.where(gbps > 0.0, gbps, 1.0) / 3600.0
    t = np.maximum(1, np.ceil(hours / dt - 1e-9).astype(np.int64))
    return np.where(gbps > 0.0, t, 0)


class Lane:
    """One lane's static inputs and per-tick control planes."""

    def __init__(self, spec: dict, seed: int, tables: dict):
        self.spec, self.seed = spec, int(seed)
        gr = _groups(spec, tables)
        G = self.G = len(gr)
        # groups whose NAT drops the pilots' lease renewals
        self.nat_g = np.array([spec["lease_interval_s"] >= g["nat"]
                               for g in gr], np.int64)
        self.nat_any = bool(self.nat_g.any())
        self.g_provider = [g["provider"] for g in gr]
        self.providers = list(dict.fromkeys(self.g_provider))
        self.prov_of_g = np.array([self.providers.index(n)
                                   for n in self.g_provider])
        self.dt = float(spec["dt_h"])
        times, now = [], 0.0
        while now < float(spec["duration_h"]):
            times.append(now)
            now += self.dt
        self.tick_times = np.array(times)
        N = self.N = len(times)
        rate = np.array([g["price"] / 24.0 for g in gr])
        self.cap = np.array([g["cap"] for g in gr], np.int64)
        self.pre_rate = np.array([g["pre_rate"] for g in gr], np.float32)
        self.pre_scale = np.array([g["pre_scale"] for g in gr], np.float32)
        self.downscale = int(spec["downscale_target"])
        self.floor = np.float32(spec["budget_floor_fraction"])

        evs = _compile_timeline(spec["timeline"])
        ft = np.searchsorted(self.tick_times, [e[0] for e in evs], "left")
        self.by_tick: Dict[int, list] = {}
        for (_t, op, arg), f in zip(evs, ft):
            if f < N:
                self.by_tick.setdefault(int(f), []).append((op, arg))

        # per-tick control state after the tick's own events
        self.rate = np.zeros((N, G), np.float32)
        self.outage = np.zeros(N, bool)
        self.n_unc = np.full(N, -1, np.int64)
        self.n_cap = np.full(N, -1, np.int64)
        self.origin_up = np.ones((N, G), bool)
        degrade = np.ones((N, G))
        self.flush = np.zeros((N, G), bool)
        base_g = [n.split("/", 1)[0] for n in self.g_provider]
        up, deg, out = np.ones(G, bool), np.ones(G), False
        for i in range(N):
            for op, arg in self.by_tick.get(i, []):
                if op == "scale":
                    self.n_unc[i] = int(arg)
                    self.n_cap[i] = min(int(arg), self.downscale)
                elif op == "outage_on":
                    out = True
                    self.n_unc[i] = self.n_cap[i] = 0
                elif op == "outage_off":
                    out = False
                    self.n_unc[i] = self.n_cap[i] = int(arg)
                else:
                    prov = arg[0] if op == "origin_degrade" else arg
                    gs = [g for g in range(G)
                          if base_g[g] == str(prov).split("/", 1)[0]]
                    if op == "origin_on":
                        up[gs] = False
                    elif op == "origin_off":
                        up[gs] = True
                    elif op == "origin_degrade":
                        deg[gs] *= float(arg[1])
                    else:
                        self.flush[i, gs] = True
            self.rate[i] = rate
            self.outage[i] = out
            self.origin_up[i] = up
            degrade[i] = deg

        wall, ckpt = float(spec["job_wall_h"]), float(spec["job_checkpoint_h"])
        L = self.L = max(1, int(np.floor(wall / ckpt) + 1))
        wfin1 = max(0, int(np.ceil(wall / self.dt - 1e-9)) - 1)
        W = wfin1 + 1
        lev_of_j = np.concatenate([np.arange(L - 1, -1, -1), [0]])
        self.w0_of_j = np.minimum(
            np.rint(lev_of_j * ckpt / self.dt).astype(np.int64), W - 1)
        self.w0_of_j[L] = 0

        dp = spec.get("dataplane")
        size = float(spec.get("job_input_gb", 0.0))
        origins = (dp or {}).get("origins") or {}
        org = [origins.get(n) or origins.get(n.split("/", 1)[0])
               for n in self.g_provider]
        self.dp_gating = bool(origins)
        self.dp_staging = self.dp_gating and size > 0.0
        S_max = 0
        if self.dp_staging:
            self.dp_has = np.array([o is not None for o in org], np.float32)
            self.dp_r = np.array([o["cache_hit_rate"] if o else 0.0
                                  for o in org], np.float32)
            self.dp_usd_miss = np.array(
                [size * o["egress_usd_per_gb"] if o else 0.0 for o in org],
                np.float32)
            n_ = np.arange(1, 201)[:, None]
            self.dp_loss = np.where(
                self.dp_r > 0.0,
                np.modf(n_ * self.dp_r[None, :].astype(np.float64))[0]
                .mean(0), 0.0).astype(np.float32)
            bw = np.array([o["bandwidth_gbps"] if o else 0.0 for o in org])
            hbw = np.array([(o["cache_bandwidth_gbps"]
                             if o["cache_bandwidth_gbps"] > 0.0
                             else o["bandwidth_gbps"]) if o else 0.0
                            for o in org])
            self.S_hit = _stage_ticks(size, hbw, self.dt)
            self.S_miss = _stage_ticks(size, bw[None, :] * degrade, self.dt)
            S_max = int(max(self.S_hit.max(), self.S_miss.max()))
        self.S_max = S_max
        We = self.W = W + S_max
        self.finmask = (np.arange(We) >= S_max + wfin1).astype(np.int32)
        self.lvl_of_w = np.minimum(np.floor(
            np.clip(np.arange(We) - S_max, 0, None) * self.dt / ckpt
            + 1e-9).astype(np.int64), L - 1)
        self.overhead = np.float32(spec["overhead_per_day"])
        self.budget = np.float32(spec["budget"])
        if not self.budget > 0:
            raise ValueError("sweep lanes need a budget")

    def shape_key(self) -> tuple:
        return (self.G, self.N, self.W, self.L, self.S_max, self.dt,
                self.dp_gating, self.dp_staging, self.nat_any,
                tuple(self.providers), tuple(self.g_provider))


# -- the tick, over K lanes at once ------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2))
def _uniforms(seeds, N: int, G: int):
    def lane(s):
        key = jax.random.PRNGKey(s)
        return jax.vmap(lambda i: jax.random.uniform(
            jax.random.fold_in(key, i), (G,)))(jnp.arange(N, dtype=jnp.int32))
    return jax.vmap(lane)(seeds)


def uniforms(seeds: Sequence[int], N: int, G: int) -> np.ndarray:
    """(K, N, G) f32: ``uniform(fold_in(PRNGKey(seed), tick), (G,))``."""
    return np.asarray(_uniforms(jnp.asarray(np.asarray(seeds, np.uint32)),
                                N, G))


def _alloc(counts, k, F):
    """Systematic proportional split of ``k`` over each row's cells."""
    tot = counts.sum(axis=-1)
    s = (np.minimum(k, tot).astype(F)
         / np.maximum(tot, 1).astype(F))[..., None]
    inc = np.cumsum(counts, axis=-1).astype(F)
    exc = inc - counts.astype(F)
    eps = F(ALLOC_EPS)
    return (np.floor(inc * s + eps) - np.floor(exc * s + eps)) \
        .astype(np.int32)


def _poisson(u, lam, F):
    from scipy.special import ndtri
    p = np.exp(-np.minimum(lam, F(POISSON_LAM_CLIP)))
    cdf = p
    kk = (u > cdf).astype(np.int32)
    for j in range(1, POISSON_TERMS):
        p = p * lam / F(j)
        cdf = cdf + p
        kk = kk + (u > cdf).astype(np.int32)
    z = ndtri(np.minimum(np.maximum(u.astype(np.float32), 1e-7),
                         1.0 - 1e-7)).astype(F)
    k_norm = np.round(lam + np.sqrt(np.maximum(lam, F(0.0))) * z)
    return np.where(lam > F(POISSON_NORMAL_ABOVE),
                    np.maximum(k_norm, F(0.0)).astype(np.int32), kk)


def _clip(a, lo, hi):
    """``np.clip`` without its promotion of bfloat16 to float32."""
    return np.minimum(np.maximum(a, lo), hi)


def _scatter_steps(val, pos, We):
    """(K,G,J) values placed at progress steps pos (K,G,J) -> (K,G,We),
    summing where two cells of J land on one step."""
    out = np.zeros(val.shape[:2] + (We,), val.dtype)
    for j in range(val.shape[2]):
        part = np.zeros_like(out)
        np.put_along_axis(part, pos[..., j:j + 1], val[..., j:j + 1], 2)
        out = out + part
    return out


def simulate(lanes: Sequence[Lane], dtype=np.float32) -> List[dict]:
    """Step K lanes of one shape through every tick; returns each
    lane's end state as host arrays."""
    F = np.dtype(dtype).type
    K = len(lanes)
    l0 = lanes[0]
    G, N, W, L = l0.G, l0.N, l0.W, l0.L
    assert all(ln.shape_key() == l0.shape_key() for ln in lanes)
    P = len(l0.providers)
    prov_groups = [l0.prov_of_g == p for p in range(P)]

    def by_provider(a):                              # (K,G) -> (K,P)
        return np.stack([a[:, m].sum(axis=1) for m in prov_groups], 1)
    u_all = uniforms([ln.seed for ln in lanes], N, G).astype(F)
    st = lambda name: np.stack([getattr(ln, name) for ln in lanes])
    rate, outage, n_unc, n_cap = st("rate").astype(F), st("outage"), \
        st("n_unc"), st("n_cap")
    cap = st("cap")                                  # (K,G) constant
    pre_rate, pre_scale = l0.pre_rate.astype(F)[None], \
        l0.pre_scale.astype(F)[None]
    downscale = st("downscale")
    floor = st("floor").astype(F)
    budget = st("budget").astype(F)
    overhead = st("overhead").astype(F)
    minq = np.array([int(ln.spec["min_queue"]) for ln in lanes])
    finmask = st("finmask")                          # (K,W)
    M_wl = np.zeros((K, W, L), np.int64)
    M_wl[np.arange(K)[:, None], np.arange(W)[None], st("lvl_of_w")] = 1
    w0 = st("w0_of_j")                               # (K,L+1)
    dt = F(l0.dt)
    thr = np.array(THRESHOLDS, F)
    dp_gating, dp_staging = l0.dp_gating, l0.dp_staging
    if l0.nat_any:
        nat_g = st("nat_g")                          # (K,G)
    if dp_gating:
        origin_up = st("origin_up")                  # (K,N,G)
    if dp_staging:
        flush = st("flush")
        r_g, has_g = l0.dp_r.astype(F)[None], l0.dp_has.astype(F)[None]
        loss_g = l0.dp_loss.astype(F)[None]
        usd_g = l0.dp_usd_miss.astype(F)[None]
        S_hit = l0.S_hit                             # (G,)
        S_miss = st("S_miss")                        # (K,N,G)
        pos_hit = l0.S_max + w0[:, None, :] - S_hit[None, :, None]

    z = lambda *s: np.zeros((K,) + s, np.int64)
    zf = lambda *s: np.zeros((K,) + s, F)
    idle, pdead, busy, target_g = z(G), z(G), z(G, W), z(G)
    lv, fresh_q = z(L), z()
    spent, by_prov, infra = zf(), zf(P), zf()
    fired = np.zeros((K, len(THRESHOLDS)), bool)
    capped, pending = np.zeros(K, bool), np.zeros(K, bool)
    cap_tick = np.full(K, -1, np.int64)
    pre_ct, nat_ct, fin_ct = z(), z(), z()
    accel, busy_h, busy_prov = zf(), zf(), zf(P)
    hit_acc, virgin, egress_g = zf(G), zf(G), zf(G)
    hits, misses, stage_t = zf(), zf(), zf()

    def greedy(n):
        cume = np.cumsum(cap, axis=1) - cap
        return np.clip(n[:, None] - cume, 0, cap)

    def requeue_levels(kb):
        return (kb.sum(axis=1)[:, :, None] * M_wl).sum(axis=1)

    def split(idle, pdead, busy, k):
        cells = np.concatenate([idle[..., None], pdead[..., None], busy], 2)
        killed = _alloc(cells, k, F)
        return killed[..., 0], killed[..., 1], killed[..., 2:]

    for i in range(N):
        live0 = idle + pdead + busy.sum(axis=2)
        live = live0
        if dp_staging:
            virgin = np.where(flush[:, i], live0.astype(F), virgin)

        # events: the deferred budget cap, then the tick's scale target
        target_g = np.where(pending[:, None], greedy(downscale), target_g)
        cap_tick = np.where(pending, i, cap_tick)
        n_eff = np.where(capped, n_cap[:, i], n_unc[:, i])
        target_g = np.where((n_eff >= 0)[:, None],
                            greedy(np.maximum(n_eff, 0)), target_g)

        # kill down to target; busy kills requeue at their level
        ki, kp, kb = split(idle, pdead, busy, np.clip(live - target_g, 0,
                                                       None))
        idle, pdead, busy = idle - ki, pdead - kp, busy - kb
        pre_ct = pre_ct + kb.sum(axis=(1, 2))
        lv = lv + requeue_levels(kb)
        live = live - ki - kp - kb.sum(axis=2)
        if dp_staging:
            virgin = virgin * live.astype(F) \
                / np.maximum(F(1.0), live0.astype(F))

        # spawn to min(target, capacity); fresh pilots arrive idle
        deficit = np.clip(np.minimum(target_g, cap) - live, 0, None)
        idle = idle + deficit
        live = live + deficit
        if dp_staging:
            virgin = virgin + deficit.astype(F)
            live_sp = live

        # preemption: Poisson total per group, split over its cells
        util = live.astype(F) / np.maximum(1, cap).astype(F)
        hazard = pre_rate * (F(1.0) + (pre_scale - F(1.0)) * util) * dt
        k_pre = _poisson(u_all[:, i], live.astype(F) * hazard, F)
        ki, kp, kb = split(idle, pdead, busy, k_pre)
        idle, pdead, busy = idle - ki, pdead - kp, busy - kb
        pre_ct = pre_ct + kb.sum(axis=(1, 2))
        lv = lv + requeue_levels(kb)
        live = live - ki - kp - kb.sum(axis=2)
        if dp_staging:
            virgin = virgin * live.astype(F) \
                / np.maximum(F(1.0), live_sp.astype(F))

        # queue top-up, then match: requeued levels first (highest
        # first), then fresh; joint (group x queue slice) = overlap of
        # the two cumulative partitions of [0, k)
        ring = lv.sum(axis=1)
        fresh_q = fresh_q + np.clip(minq - (ring + fresh_q), 0, None)
        idle_m = idle * origin_up[:, i] if dp_gating else idle
        k = np.minimum(idle_m.sum(axis=1), ring + fresh_q)
        k = np.where(outage[:, i], 0, k)
        take_g = _alloc(idle_m, k, F)
        avail = np.concatenate([lv[:, ::-1], fresh_q[:, None]], axis=1)
        cumq = np.cumsum(avail, axis=1)
        take_j = np.clip(k[:, None] - (cumq - avail), 0, avail)
        cA, cB = np.cumsum(take_g, axis=1), np.cumsum(take_j, axis=1)
        lo = np.maximum((cA - take_g)[:, :, None], (cB - take_j)[:, None])
        hi = np.minimum(cA[:, :, None], cB[:, None, :])
        joint = np.clip(hi - lo, 0, None)            # (K,G,L+1)
        if dp_staging:
            take_f = take_g.astype(F)
            first_f = np.minimum(take_f, virgin)
            virgin = virgin - first_f
            acc = hit_acc + take_f * r_g - first_f * loss_g
            th_f = _clip(np.floor(acc), F(0.0), take_f)
            hit_acc = acc - th_f
            th = th_f.astype(np.int64)
            cumj = np.cumsum(joint, axis=2)
            hit_j = np.clip(th[:, :, None] - (cumj - joint), 0, joint)
            miss_j = joint - hit_j
            pos_miss = l0.S_max + w0[:, None, :] - S_miss[:, i][:, :, None]
            busy = busy + _scatter_steps(hit_j, np.broadcast_to(
                pos_hit, hit_j.shape), W) + _scatter_steps(miss_j, pos_miss, W)
            hits = hits + (th_f * has_g).sum(axis=1)
            misses = misses + ((take_f - th_f) * has_g).sum(axis=1)
            stage_t = stage_t + (th_f * S_hit.astype(F)[None]
                                 + (take_f - th_f)
                                 * S_miss[:, i].astype(F)).sum(axis=1)
            eg_g = (take_f - th_f) * usd_g
            egress_g = egress_g + eg_g
            eg = eg_g.sum(axis=1)
        else:
            busy = busy + _scatter_steps(
                joint, np.broadcast_to(w0[:, None, :], joint.shape), W)
            eg = zf()
        idle = idle - take_g
        lv = lv - take_j[:, :L][:, ::-1]
        fresh_q = fresh_q - take_j[:, L]

        # NAT drops: busy pilots of disconnected groups requeue their
        # jobs at their level; the instance stays live with a dead pilot
        if l0.nat_any:
            drop = busy * nat_g[:, :, None]
            n_drop = drop.sum(axis=(1, 2))
            lv = lv + requeue_levels(drop)
            nat_ct = nat_ct + n_drop
            pre_ct = pre_ct + n_drop
            busy = busy - drop
            pdead = pdead + drop.sum(axis=2)

        # advance one dt step; finishes release the pilot
        fin = busy * finmask[:, None, :]
        rest = busy - fin
        busy = np.concatenate([np.zeros_like(rest[..., :1]),
                               rest[..., :-1]], axis=2)
        fin_g = fin.sum(axis=2)
        fin_ct = fin_ct + fin_g.sum(axis=1)
        idle = idle + fin_g

        # bill the interval ending now against the tick's starting set
        dh = dt if i > 0 else F(0.0)
        amt = live0.astype(F) * (rate[:, i] * dh)
        spent = spent + amt.sum(axis=1) + eg
        by_prov = by_prov + by_provider(amt)
        oh = overhead * dt / F(24.0)
        spent = spent + np.where(oh > F(0.0), oh, F(0.0))
        infra = infra + np.where(oh > F(0.0), oh, F(0.0))

        # ledger thresholds -> budget-floor tripwire (cap next tick)
        frac = np.maximum(F(0.0), budget - spent) / budget
        cross = (frac[:, None] <= thr[None]) & ~fired
        newly = cross.any(axis=1)
        fired = fired | cross
        pending = newly & (frac <= floor) & ~capped
        capped = capped | pending

        # GPU time at end-of-tick occupancy
        busy_g = busy.sum(axis=2).astype(F)
        live_end = (idle + pdead).astype(F) + busy_g
        accel = accel + live_end.sum(axis=1) * dt
        busy_h = busy_h + busy_g.sum(axis=1) * dt
        busy_prov = busy_prov + by_provider(busy_g) * dt

    live_final = idle + pdead + busy.sum(axis=2)
    amt = live_final.astype(F) * rate[:, -1] * dt
    spent = spent + amt.sum(axis=1)
    by_prov = by_prov + by_provider(amt)
    for name, a in (("spent", spent), ("accel", accel), ("virgin", virgin),
                    ("egress", egress_g), ("by_prov", by_prov)):
        if a.dtype != np.dtype(F):
            raise TypeError(f"reference: {name} left {np.dtype(F)} "
                            f"for {a.dtype}")
    return [dict(spent=float(spent[b]), accel=float(accel[b]),
                 busy_h=float(busy_h[b]), pre_ct=int(pre_ct[b]),
                 nat_ct=int(nat_ct[b]), fin_ct=int(fin_ct[b]),
                 egress=float(egress_g[b].astype(np.float32).sum()),
                 stage_t=float(stage_t[b]), hits=float(hits[b]),
                 misses=float(misses[b]), cap_tick=int(cap_tick[b]),
                 live_g=live_final[b].tolist())
            for b in range(K)]


# -- results and provenance in the sweep's row schema -------------------------

def lane_events(ln: Lane, cap_tick: int) -> List[dict]:
    """``events_fired``: each op's record at its fire tick, the budget
    cap first at the tick the tripwire applied it."""
    ticks = sorted(set(ln.by_tick) | ({cap_tick} if cap_tick >= 0
                                      else set()))
    recs = []
    for ft in ticks:
        now = float(ln.tick_times[ft])
        capped = 0 <= cap_tick <= ft
        if ft == cap_tick:
            recs.append({"t": now, "event": "budget_floor",
                         "target": ln.downscale})
        for op, arg in ln.by_tick.get(ft, []):
            if op == "scale":
                tgt = min(int(arg), ln.downscale) if capped else int(arg)
                recs.append({"t": now, "event": "scale", "target": tgt})
            elif op == "outage_on":
                recs.append({"t": now, "event": "outage_on"})
            elif op == "outage_off":
                recs.append({"t": now, "event": "outage_off",
                             "target": int(arg)})
            elif op == "origin_on":
                recs.append({"t": now, "event": "origin_outage_on",
                             "provider": str(arg)})
            elif op == "origin_off":
                recs.append({"t": now, "event": "origin_outage_off",
                             "provider": str(arg)})
            elif op == "origin_degrade":
                recs.append({"t": now, "event": "origin_degrade",
                             "provider": str(arg[0]),
                             "factor": float(arg[1])})
            else:
                recs.append({"t": now, "event": "cache_flush",
                             "provider": str(arg)})
    return recs


def lane_row(ln: Lane, end: dict) -> dict:
    """The compared part of a sweep row."""
    by_provider: Dict[str, int] = {}
    for g, name in enumerate(ln.g_provider):
        by_provider[name] = by_provider.get(name, 0) + end["live_g"][g]
    seen = end["hits"] + end["misses"]
    return {
        "cost": round(end["spent"], 2),
        "accel_days": round(end["accel"] / 24.0, 1),
        "busy_hours": round(end["busy_h"], 1),
        "jobs_finished": end["fin_ct"],
        "preemptions": end["pre_ct"],
        "nat_drops": end["nat_ct"],
        "egress_usd": round(end["egress"], 2),
        "stagein_hours": round(end["stage_t"] * ln.dt, 1),
        "cache_hit_fraction": round(end["hits"] / seen, 4) if seen else 0.0,
        "by_provider": by_provider,
        "events_fired": lane_events(ln, end["cap_tick"]),
    }


def reference_rows(lanes: Sequence[tuple], tables: dict,
                   dtype=np.float32) -> List[dict]:
    """Rows for ``(spec_dict, seed)`` lanes, in input order; lanes of
    one shape are stepped together."""
    built = [Lane(spec, seed, tables) for spec, seed in lanes]
    groups: Dict[tuple, List[int]] = {}
    for i, ln in enumerate(built):
        groups.setdefault(ln.shape_key(), []).append(i)
    rows: List[dict] = [None] * len(built)
    # a lower precision overflows on purpose; float32 must not (its
    # Poisson terms underflow to 0 as the program's do)
    quiet = np.dtype(dtype) != np.float32
    for idx in groups.values():
        with np.errstate(all="ignore") if quiet \
                else np.errstate(under="ignore"):
            ends = simulate([built[i] for i in idx], dtype)
        for i, end in zip(idx, ends):
            rows[i] = lane_row(built[i], end)
    return rows
