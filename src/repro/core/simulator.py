"""Discrete-event simulator for the cloud pool: preemptions, billing,
pilots, jobs — deterministic (seeded numpy), hour-granular.

Drives provisioner + overlay + budget together so ``spec.run_solo`` can
replay the paper's two-week exercise and compare simulated totals
(GPU-days, $, EFLOP-hours, preemption counts) against the paper's
published numbers (§IV/§V).

Two interchangeable engines drive the tick:

  * ``engine="array"`` (default): the vectorized struct-of-arrays engine
    (core/fleet.py) — instances/pilots/jobs live in parallel numpy arrays
    and every phase of the tick is an array op.  This is what makes
    100k-instance campaigns tractable.
  * ``engine="object"``: the seed dataclass engine (one Python object per
    instance/pilot/job).  Kept as the executable specification; the two
    engines consume the RNG identically and produce matching results
    (tests/test_fleet_engine.py).

``sim.prov`` and ``sim.ce`` expose the same API either way (the array
engine provides thin dataclass view layers), so the timeline controller,
the examples and the tests are engine-agnostic.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.core.budget import BudgetLedger
from repro.core.dataplane import DataPlane, DataPlaneRuntime
from repro.core.overlay import ComputeElement, Job
from repro.core.provider import T4_FP32_TFLOPS, ProviderSpec
from repro.core.provisioner import MultiCloudProvisioner


@dataclass
class SimConfig:
    duration_h: float = 14 * 24.0
    dt_h: float = 0.25                  # 15-minute ticks
    seed: int = 2021
    lease_interval_s: float = 120.0     # < Azure NAT 240 s (post-fix default)
    job_wall_h: float = 4.0             # typical IceCube GPU task length
    job_checkpoint_h: float = 1.0
    accel_tflops: float = T4_FP32_TFLOPS
    overhead_per_day: float = 390.0     # CE VM, storage, egress ("all
    #                                     included" in the paper's $58k)
    min_queue: int = 4000               # CE queue top-up level per tick
    engine: str = "array"               # "array" (vectorized) | "object"
    spot: bool = True                   # spot (default) vs on-demand pricing
    job_input_gb: float = 0.0           # staged in before a job starts ...
    dataplane: Optional[DataPlane] = None  # ... against these origins

    @classmethod
    def from_spec(cls, spec, seed: int,
                  engine: Optional[str] = None) -> "SimConfig":
        """Engine knobs of a ``repro.core.spec.CampaignSpec``.  ``seed``
        must be an integer: a float like 3.7 would previously truncate to
        3 via ``int()`` and silently run a different campaign, and a bool
        (``True`` is an ``int`` subclass) would silently run seed 1."""
        if isinstance(seed, bool) or not isinstance(
                seed, (int, np.integer)):
            raise TypeError(
                f"seed must be an integer, got {seed!r} "
                f"({type(seed).__name__}); float/bool seeds would be "
                "silently coerced to a different campaign")
        return cls(duration_h=spec.duration_h, dt_h=spec.dt_h,
                   seed=seed, lease_interval_s=spec.lease_interval_s,
                   job_wall_h=spec.job_wall_h,
                   job_checkpoint_h=spec.job_checkpoint_h,
                   accel_tflops=spec.accel_tflops,
                   overhead_per_day=spec.overhead_per_day,
                   min_queue=spec.min_queue, spot=spec.spot,
                   job_input_gb=getattr(spec, "job_input_gb", 0.0),
                   dataplane=getattr(spec, "dataplane", None),
                   engine=engine or cls.engine)


@dataclass
class TickStats:
    t_h: float
    running: int
    busy: int
    queued: int
    spent: float
    preemptions: int


class CloudSimulator:
    def __init__(self, catalog: Dict[str, ProviderSpec], budget: float,
                 cfg: SimConfig = SimConfig(),
                 engine: Optional[str] = None, recorder=None):
        self.cfg = cfg
        self.rng = np.random.default_rng(cfg.seed)
        self.ledger = BudgetLedger(budget)
        self.engine_kind = engine or cfg.engine
        # recorder: optional events.TraceRecorder collecting the typed
        # instance/pilot/job event stream (spec.run_solo(collect="trace"))
        self.recorder = recorder
        # always constructed (empty plane when the spec has none) so the
        # OriginOutage/OriginDegrade/CacheFlush timeline ops land
        # identically — as no-ops — on dataplane-less campaigns too
        self.dataplane = DataPlaneRuntime(cfg.dataplane, cfg.job_input_gb,
                                          cfg.dt_h)
        if self.engine_kind == "array":
            from repro.core.fleet import ArrayFleetEngine
            self.fleet = ArrayFleetEngine(
                catalog, self.ledger, self.rng,
                lease_interval_s=cfg.lease_interval_s, spot=cfg.spot,
                job_wall_h=cfg.job_wall_h,
                job_checkpoint_h=cfg.job_checkpoint_h, recorder=recorder,
                dataplane=self.dataplane)
            self.prov = self.fleet.prov
            self.ce = self.fleet.ce
        elif self.engine_kind == "object":
            self.fleet = None
            self.prov = MultiCloudProvisioner(catalog, self.ledger,
                                              spot=cfg.spot,
                                              recorder=recorder)
            self.ce = ComputeElement(lease_interval_s=cfg.lease_interval_s,
                                     recorder=recorder,
                                     dataplane=self.dataplane)
        else:
            raise ValueError(f"unknown engine {self.engine_kind!r}")
        self.now = 0.0
        self.history: List[TickStats] = []
        self._pilot_by_instance: Dict[int, int] = {}
        self._events: List[tuple] = []   # (t_h, callable) one-shots
        # request-rate factor (spec.WorkloadCurve): the CE queue tops up
        # to int(min_queue * factor).  Set only at event time, so the
        # per-tick int(int * float) product matches the batched engine's
        # event-time cache bit-for-bit.
        self.workload_factor = 1.0
        self.accel_hours = 0.0           # delivered accelerator wall hours
        self.busy_hours = 0.0            # hours with a job attached
        self.busy_hours_by_provider: Dict[str, float] = {}

    @classmethod
    def from_spec(cls, spec, seed: int, engine: Optional[str] = None,
                  recorder=None) -> "CloudSimulator":
        """Build a simulator straight from a declarative
        ``repro.core.spec.CampaignSpec`` (catalog + engine knobs); the
        spec's *timeline* is installed by ``spec.TimelineController``."""
        from repro.core.spec import build_catalog
        cfg = SimConfig.from_spec(spec, seed)
        return cls(build_catalog(spec), spec.budget, cfg, engine=engine,
                   recorder=recorder)

    # -- scheduling ---------------------------------------------------------
    def at(self, t_h: float, fn: Callable[["CloudSimulator"], None]):
        self._events.append((t_h, fn))
        self._events.sort(key=lambda e: e[0])

    def effective_min_queue(self) -> int:
        """The CE queue top-up level under the current request-rate
        factor (1.0 unless a ``WorkloadCurve`` event changed it)."""
        return int(self.cfg.min_queue * self.workload_factor)

    def ensure_jobs(self, min_queue: Optional[int] = None):
        """IceCube's queue was effectively infinite; keep it topped up."""
        mq = self.effective_min_queue() if min_queue is None else min_queue
        if self.fleet is not None:
            self.fleet.ensure_jobs(mq)
            return
        need = mq - len(self.ce.queue)
        for _ in range(max(0, need)):
            self.ce.submit(Job(id=self.ce.next_job_id(),
                               wall_h=self.cfg.job_wall_h,
                               checkpoint_period_h=self.cfg.job_checkpoint_h))

    # -- object-engine tick phases -----------------------------------------
    def _sync_pilots(self):
        """Every live instance runs exactly one registered pilot; pilots on
        stopped/preempted instances are reaped (their jobs re-queue)."""
        live_ids = set()
        for inst in self.prov.live_instances():
            live_ids.add(inst.id)
            if inst.id not in self._pilot_by_instance:
                nat = self.prov.catalog[inst.provider].nat_idle_timeout_s
                p = self.ce.register_pilot(inst.id, inst.provider, nat,
                                           self.now)
                self._pilot_by_instance[inst.id] = p.id
        for iid in list(self._pilot_by_instance):
            if iid not in live_ids:
                self.ce.pilot_lost(self._pilot_by_instance.pop(iid),
                                   self.now)

    def _maintain_groups(self):
        """Group mechanisms keep their desired count: replacements for
        preempted instances are provisioned automatically (paper §II: 'no
        further operator intervention was needed')."""
        for g in self.prov.groups:
            if len(g.running) < min(g.target, g.region.capacity):
                g.set_target(g.target, self.now)

    def _sample_preemptions(self, dt_h: float):
        from repro.core.fleet import preemption_rate
        for g in self.prov.groups:
            rate = preemption_rate(g.region.preempt_rate_per_hour,
                                   g.region.preempt_scale_at_full,
                                   len(g.running), g.region.capacity)
            for inst in g.running:
                if self.rng.random() < rate * dt_h:
                    g.preempt(inst.id, self.now)
                    pid = self._pilot_by_instance.pop(inst.id, None)
                    if pid is not None:
                        self.ce.pilot_lost(pid, self.now)

    def step(self):
        dt = self.cfg.dt_h
        # one-shot events
        while self._events and self._events[0][0] <= self.now:
            _, fn = self._events.pop(0)
            fn(self)
        if self.fleet is not None:
            running, busy = self.fleet.tick(self.now, dt,
                                            self.effective_min_queue())
            busy_by_prov = self.fleet.busy_by_provider()
        else:
            self._maintain_groups()
            self._sync_pilots()
            self._sample_preemptions(dt)
            self._sync_pilots()
            self.ensure_jobs()
            self.ce.match(self.now)
            self.ce.advance(dt, self.now)
            self.prov.bill(self.now)
            running = self.prov.total_running()
            busy = self.ce.stats()["pilots_busy"]
            busy_by_prov = self.ce.busy_by_provider()
        # cache-miss egress lands right after the GPU-hour charges and
        # before the overhead line — the engine-shared billing order
        self.dataplane.bill(self.ledger, self.now, self.recorder)
        if self.cfg.overhead_per_day > 0:
            self.ledger.charge("infra", self.cfg.overhead_per_day * dt / 24.0,
                               self.now, note="CE VM, storage, egress")
        self.accel_hours += running * dt
        self.busy_hours += busy * dt
        for prov_name, n in busy_by_prov.items():
            self.busy_hours_by_provider[prov_name] = \
                self.busy_hours_by_provider.get(prov_name, 0.0) + n * dt
        self.history.append(TickStats(self.now, running, busy,
                                      len(self.ce.queue),
                                      self.ledger.spent,
                                      self.ce.preemption_events))
        self.now += dt

    def run_until(self, t_h: float):
        while self.now < min(t_h, self.cfg.duration_h):
            self.step()

    # -- results ---------------------------------------------------------------
    def settle(self):
        """Bill any instance-hours accrued since the last tick (found by
        tests/test_sim_properties.py::test_sim_conservation: the final
        tick's interval was never charged)."""
        self.prov.bill(self.now)

    def _eflop_hours(self) -> float:
        """fp32 EFLOP-hours delivered.  Homogeneous catalogs (no
        per-provider fp32_tflops) use the seed formula; heterogeneous
        catalogs weight each provider's busy hours by its GPU's peak.
        Sub-GPU slices (spec.GpuSlicing) flow through the heterogeneous
        path: a ``name/k`` provider carries a 1/k-scaled fp32_tflops, so
        slice-hours aggregate to the same device-hours of compute."""
        specs = self.prov.catalog.values()
        if not any(p.fp32_tflops is not None for p in specs):
            return self.busy_hours * self.cfg.accel_tflops * 1e12 / 1e18
        tflops = {p.name: (p.fp32_tflops if p.fp32_tflops is not None
                           else self.cfg.accel_tflops) for p in specs}
        return sum(h * tflops.get(name, self.cfg.accel_tflops)
                   for name, h in self.busy_hours_by_provider.items()
                   ) * 1e12 / 1e18

    def results(self) -> dict:
        self.settle()
        return {
            "accel_hours": round(self.accel_hours, 1),
            "accel_days": round(self.accel_hours / 24.0, 1),
            "busy_hours": round(self.busy_hours, 1),
            "busy_hours_by_provider": {
                k: round(v, 1)
                for k, v in sorted(self.busy_hours_by_provider.items())},
            "eflop_hours_fp32": round(self._eflop_hours(), 3),
            "cost": round(self.ledger.spent, 2),
            "cost_per_accel_day": round(
                self.ledger.spent / max(self.accel_hours / 24.0, 1e-9), 2),
            "preemptions": self.ce.preemption_events,
            "nat_drops": self.ce.nat_drop_events,
            "jobs_finished": len(self.ce.finished),
            "budget": self.ledger.report(),
            "by_provider": self.prov.running_by_provider(),
            **self.dataplane.results(),
        }
