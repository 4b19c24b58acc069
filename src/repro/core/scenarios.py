"""What-if scenario library for pre-burst planning sweeps.

The paper's headline numbers come from a *single* two-week run; HEPCloud-
style pre-burst planning (Holzman et al. 2017) and per-scenario cost
studies (Sfiligoi et al. 2022) want Monte-Carlo sweeps over seeds and
operational what-ifs.  Each library function below returns ready-made
:class:`~repro.core.spec.CampaignSpec` variants — catalog, spot mix,
budget floor, and declarative timeline events (ramp steps, CE outages,
price/capacity shifts) — that every execution path understands:

  * solo: ``api.run(spec, seeds=seed)`` drives one ``CloudSimulator``
    campaign (the reference semantics), and
  * batched: ``api.run(specs, seeds=seeds)`` ticks all (spec, seed)
    lanes in lock-step as one array program, bit-reproducible against
    the solo run at the same (seed, spec).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from repro.core.spec import (CacheFlush, CampaignSpec, CEOutage, DataOrigin,
                             DataPlane, GpuSlicing, OriginDegrade,
                             OriginOutage, PAPER_RAMP_EVENTS, PAPER_TIMELINE,
                             PriceCurve, WorkloadCurve, paper_spec)

# the paper CE outage's resume target (~20 % budget left): every
# outage-grid member resumes where the paper's did
POST_OUTAGE_TARGET = PAPER_TIMELINE[-1].resume_target


# -- the library (all entries are CampaignSpecs) ---------------------------

def paper_baseline() -> CampaignSpec:
    return paper_spec()


def ondemand_fallback(budget: float = 58000.0) -> CampaignSpec:
    """All on-demand: zero preemptions, ~4.4x the $/GPU-day — how far does
    the same budget get without spot risk?"""
    return paper_spec(name="ondemand", spot=False, budget=budget)


def spot_ondemand_mixes(fracs: Sequence[float] = (0.1, 0.25, 0.5)
                        ) -> List[CampaignSpec]:
    return [paper_spec(name=f"mix-od{int(f * 100):02d}",
                       ondemand_fraction=f) for f in fracs]


def heterogeneous_burst(capacity_scale: float = 1.0) -> CampaignSpec:
    """The §III mixed T4/V100/P100/M60 pool under the paper's controller."""
    return paper_spec(name="hetero", catalog="heterogeneous",
                      capacity_scale=capacity_scale)


def outage_grid(times_h: Sequence[float] = (60.0, 252.0, 300.0),
                durations_h: Sequence[float] = (2.0, 12.0)
                ) -> List[CampaignSpec]:
    """What if the CE had died earlier / stayed down longer?"""
    # keep the declared timeline time-sorted (lint SPEC103): the outage
    # lands mid-ramp, not appended after the 192 h ramp steps
    return [paper_spec(name=f"outage-t{int(t)}-d{int(d)}",
                       timeline=tuple(sorted(
                           PAPER_RAMP_EVENTS + (
                               CEOutage(t, d, POST_OUTAGE_TARGET),),
                           key=lambda ev: ev.at_h)))
            for t in times_h for d in durations_h]


def outage_burst(at_h: float = 60.0, duration_h: float = 6.0
                 ) -> CampaignSpec:
    """One outage-grid member as a single named spec — the
    preemption-bearing campaign the elastic-goodput path replays:
    ``api.run(outage_burst(), collect="trace")`` ->
    ``elastic.drive_pool(result.trace, pool, runner)`` (see
    examples/elastic_goodput.py).  Defaults match the
    ``outage-t60-d6`` entry of :func:`default_suite`."""
    return outage_grid((at_h,), (duration_h,))[0]


def budget_floor_variants(floors: Sequence[float] = (0.1, 0.2, 0.3)
                          ) -> List[CampaignSpec]:
    """How early the 'downscale to 1k' tripwire fires vs GPU-days kept."""
    return [paper_spec(name=f"floor{int(f * 100):02d}",
                       budget_floor_fraction=f) for f in floors]


def price_perturbations(factors: Sequence[float] = (0.8, 1.0, 1.25)
                        ) -> List[CampaignSpec]:
    """Uniform spot-price-curve shifts (market drift between planning and
    burst day)."""
    return [paper_spec(name=f"price{int(f * 100):03d}", price_scale=f)
            for f in factors]


# named multi-day market curves for the paper's two-week window
# (piecewise-constant daily factors; the paper priced everything off the
# burst-day spot rate — these ask what the drift it ignored would cost)
MARKET_CURVES: Dict[str, PriceCurve] = {
    # steady upward drift as the burst itself tightens the spot pools
    "drift-up": PriceCurve(((72.0, 1.1), (144.0, 1.25), (240.0, 1.4))),
    # weekday-peak / weekend-dip rhythm
    "weekend-dip": PriceCurve(((96.0, 0.85), (144.0, 1.0),
                               (264.0, 0.85))),
    # the favored provider gets squeezed mid-burst, others stay flat
    "azure-squeeze": PriceCurve(((120.0, 1.5), (216.0, 1.1)),
                                provider="azure"),
}


def _sorted_timeline(*events):
    """Anchor-time-sorted (lint-clean) timeline; engines tie-break
    stably by declaration position either way."""
    return tuple(sorted(events, key=lambda e: e.at_h))


def price_curve_scenarios(curves: Sequence[str] = tuple(MARKET_CURVES)
                          ) -> List[CampaignSpec]:
    """The paper burst priced under realistic *drifting* spot markets:
    each variant weaves one named multi-day ``PriceCurve`` into the
    paper timeline (first-class spec data — serializable, sweepable)."""
    return [paper_spec(name=f"curve-{name}",
                       timeline=_sorted_timeline(*PAPER_TIMELINE,
                                                 MARKET_CURVES[name]))
            for name in curves]


# named request-rate curves (piecewise-constant factors on the CE queue
# top-up level).  The paper treated the job supply as infinite; these ask
# the HEPCloud cost question of *serving* load — what the same pool costs
# when demand breathes.  Factors below ~0.03 starve the queue at full
# fleet (int(4000 * f) jobs vs ~125 matched per tick at 2000 pilots).
WORKLOAD_CURVES: Dict[str, WorkloadCurve] = {
    # office-hours rhythm over the two-week window: full demand from
    # 08:00, near-idle troughs from 20:00 each day
    "diurnal": WorkloadCurve(tuple(
        p for d in range(14)
        for p in ((24.0 * d + 8.0, 1.0), (24.0 * d + 20.0, 0.02)))),
    # near-idle background, then a 12 h flash crowd mid-burst
    "flash-crowd": WorkloadCurve(((0.0, 0.05), (120.0, 1.0),
                                  (132.0, 0.05))),
}


def workload_curve_scenarios(curves: Sequence[str] = tuple(WORKLOAD_CURVES)
                             ) -> List[CampaignSpec]:
    """The paper burst serving *time-varying* demand: each variant weaves
    one named ``WorkloadCurve`` into the paper timeline, scaling the job
    arrival rate all three engines see bit-identically."""
    return [paper_spec(name=f"load-{name}",
                       timeline=_sorted_timeline(*PAPER_TIMELINE,
                                                 WORKLOAD_CURVES[name]))
            for name in curves]


def workload_burst() -> CampaignSpec:
    """Demand and market shifting at once — the WorkloadCurve golden
    campaign (tests/data/workload_curve.spec.json, pinned at seed 2021):
    the paper burst under a drifting spot market while serving a
    flash-crowd demand profile."""
    return paper_spec(
        name="workload-burst",
        timeline=_sorted_timeline(*PAPER_TIMELINE,
                                  MARKET_CURVES["drift-up"],
                                  WORKLOAD_CURVES["flash-crowd"]))


def gpu_slicing_variants(slices: Sequence[int] = (2, 4, 7)
                         ) -> List[CampaignSpec]:
    """Sfiligoi 2022 sub-GPU accounting: the same burst planned in
    1/2..1/7-GPU slices (k-fold capacity at ~1/k price and TFLOPS per
    slot) instead of whole devices."""
    return [paper_spec(name=f"slice{k}",
                       gpu_slicing=GpuSlicing(slices=k)) for k in slices]


def curve_sliced_burst(slices: int = 4) -> CampaignSpec:
    """Both new surfaces at once — the golden regression campaign
    (tests/data/curve_sliced.spec.json, pinned at seed 2021): the §III
    heterogeneous pool in 1/4-GPU slices, priced under a drifting
    market plus a provider-targeted squeeze on the sliced Azure T4
    pool."""
    return paper_spec(
        name="curve-sliced", catalog="heterogeneous",
        gpu_slicing=GpuSlicing(slices=slices),
        timeline=_sorted_timeline(
            *PAPER_TIMELINE, MARKET_CURVES["drift-up"],
            PriceCurve(((120.0, 1.5), (216.0, 1.1)),
                       provider=f"azure-t4/{slices}")))


# named data-plane layouts for the paper's t4 catalog (azure/gcp/aws).
# The paper treated jobs as pure compute; the follow-on IceCube data-
# federation work (arXiv 2308.07999) and HEPCloud's egress accounting
# (arXiv 1710.00100) make stage-in bandwidth, cache tiers and per-GB
# egress first-order campaign inputs — these origin maps price them.
DATA_PLANES: Dict[str, DataPlane] = {
    # one well-connected origin per cloud, regional caches on the two
    # majority providers; azure (the paper's favored pool) pays the
    # steepest per-GB egress on misses
    "federated": DataPlane({
        "azure": DataOrigin(bandwidth_gbps=4.0, egress_usd_per_gb=0.087,
                            cache_hit_rate=0.7,
                            cache_bandwidth_gbps=16.0),
        "gcp": DataOrigin(bandwidth_gbps=3.0, egress_usd_per_gb=0.12,
                          cache_hit_rate=0.5, cache_bandwidth_gbps=12.0),
        "aws": DataOrigin(bandwidth_gbps=3.0, egress_usd_per_gb=0.09),
    }),
    # cache-less worst case: every stage-in streams from the origin
    # and pays egress — the upper bound on the data bill
    "no-cache": DataPlane({
        "azure": DataOrigin(bandwidth_gbps=4.0, egress_usd_per_gb=0.087),
        "gcp": DataOrigin(bandwidth_gbps=3.0, egress_usd_per_gb=0.12),
        "aws": DataOrigin(bandwidth_gbps=3.0, egress_usd_per_gb=0.09),
    }),
}


def data_heavy_mix(sizes_gb: Sequence[float] = (2.0, 25.0, 100.0),
                   plane: str = "federated") -> List[CampaignSpec]:
    """The paper burst with per-job input data: the same campaign at
    photon-table (~2 GB), typical-simulation (~25 GB) and raw-readout
    (~100 GB) stage-in sizes — how fast does goodput become
    bandwidth-bound, and what does the egress line item grow to?"""
    return [paper_spec(name=f"data{int(s):03d}gb", job_input_gb=s,
                       dataplane=DATA_PLANES[plane])
            for s in sizes_gb]


def origin_outage_grid(times_h: Sequence[float] = (60.0, 252.0),
                       durations_h: Sequence[float] = (6.0, 24.0),
                       provider: str = "azure",
                       size_gb: float = 25.0) -> List[CampaignSpec]:
    """What if the favored provider's data origin — not the CE — went
    dark?  Pilots stay up and billed but take no new jobs until the
    origin recovers (the data-plane mirror of :func:`outage_grid`)."""
    return [paper_spec(
                name=f"origin-{provider}-t{int(t)}-d{int(d)}",
                job_input_gb=size_gb,
                dataplane=DATA_PLANES["federated"],
                timeline=_sorted_timeline(*PAPER_RAMP_EVENTS,
                                          OriginOutage(t, d, provider)))
            for t in times_h for d in durations_h]


def egress_cost_scenarios(size_gb: float = 25.0) -> List[CampaignSpec]:
    """The egress-bill optimization question: the same data-heavy burst
    with and without regional caches, plus a mid-burst cache flush on
    the favored provider — what do the cache tiers actually save, and
    what does re-warming after a flush cost?"""
    flush = paper_spec(
        name="egress-flushed", job_input_gb=size_gb,
        dataplane=DATA_PLANES["federated"],
        timeline=_sorted_timeline(*PAPER_TIMELINE,
                                  CacheFlush(180.0, "azure")))
    return [paper_spec(name="egress-cached", job_input_gb=size_gb,
                       dataplane=DATA_PLANES["federated"]),
            paper_spec(name="egress-nocache", job_input_gb=size_gb,
                       dataplane=DATA_PLANES["no-cache"]),
            flush]


def dataplane_burst() -> CampaignSpec:
    """The full data-plane surface in one campaign — the DataPlane
    golden (tests/data/dataplane.spec.json, pinned at seed 2021): the
    paper burst staging 25 GB per job through the federated origin map
    while the azure origin suffers a mid-burst outage, the aws WAN
    degrades for the back half, and the azure cache is flushed cold
    late in the window."""
    return paper_spec(
        name="dataplane-burst", job_input_gb=25.0,
        dataplane=DATA_PLANES["federated"],
        timeline=_sorted_timeline(*PAPER_TIMELINE,
                                  OriginOutage(98.0, 12.0, "azure"),
                                  OriginDegrade(168.0, 0.5, "aws"),
                                  CacheFlush(250.0, "azure")))


def planning_grid(price_scales: Sequence[float] = (0.8, 0.9, 1.0,
                                                   1.1, 1.25),
                  floors: Sequence[float] = (0.1, 0.2, 0.3, 0.4),
                  budgets: Sequence[float] = (40000.0, 58000.0, 80000.0)
                  ) -> List[CampaignSpec]:
    """A dense pre-burst planning grid: every (price drift x budget
    floor x budget) paper variant — 60 specs by default, ~1024 lanes at
    17 seeds.  Every member keeps the paper catalog and capacity, so the
    whole grid shares one structural batch key and ``engine="jax"``
    compiles it into a *single* scan (the batched numpy engine chunks it
    identically; it just ticks each lane from Python)."""
    return [paper_spec(
                name=f"grid-p{int(p * 100):03d}-f{int(f * 100):02d}"
                     f"-b{int(b / 1000)}k",
                price_scale=p, budget_floor_fraction=f, budget=b)
            for p in price_scales for f in floors for b in budgets]


def pareto_grid(curves: Sequence[Optional[str]] = (None, "drift-up",
                                                   "azure-squeeze"),
                slices: Sequence[int] = (1, 4),
                planes: Sequence[Optional[str]] = (None, "federated"),
                size_gb: float = 25.0) -> List[CampaignSpec]:
    """The cost-vs-goodput frontier candidate set: every (market curve
    x GPU slicing x data plane) paper variant — the axes the repo
    already prices (``MARKET_CURVES``, ``GpuSlicing``, ``DATA_PLANES``)
    composed into one sweepable grid for
    ``analysis.pareto.frontier()`` / the ``campaigns pareto`` CLI.
    ``None`` entries mean "paper baseline" on that axis; 12 specs by
    default."""
    from dataclasses import replace as _replace
    specs = []
    for c in curves:
        for k in slices:
            for plane in planes:
                kw = {}
                if c is not None:
                    curve = MARKET_CURVES[c]
                    if curve.provider is not None and k > 1:
                        # slicing renames catalog providers to "name/k";
                        # a provider-targeted curve must follow
                        curve = _replace(curve,
                                         provider=f"{curve.provider}/{k}")
                    kw["timeline"] = _sorted_timeline(*PAPER_TIMELINE,
                                                      curve)
                if k > 1:
                    kw["gpu_slicing"] = GpuSlicing(slices=k)
                if plane is not None:
                    kw["dataplane"] = DATA_PLANES[plane]
                    kw["job_input_gb"] = size_gb
                specs.append(paper_spec(
                    name=f"par-{c or 'flat'}-s{k}-{plane or 'nodata'}",
                    **kw))
    return specs


def default_suite() -> List[CampaignSpec]:
    """A representative pre-burst planning suite: the paper baseline plus
    one of each what-if family."""
    return [paper_baseline(),
            ondemand_fallback(),
            *spot_ondemand_mixes((0.25,)),
            heterogeneous_burst(),
            *outage_grid((60.0, 300.0), (6.0,)),
            *budget_floor_variants((0.3,)),
            *price_perturbations((0.8, 1.25)),
            *price_curve_scenarios(("drift-up", "azure-squeeze")),
            *workload_curve_scenarios(),
            *gpu_slicing_variants((4,)),
            *data_heavy_mix((25.0,)),
            *origin_outage_grid((60.0,), (6.0,)),
            *egress_cost_scenarios()]
