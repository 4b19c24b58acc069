"""The paper's contribution (control plane) as composable modules.

spec         — CampaignSpec: one declarative, JSON-serializable campaign
               description (catalog, fleet/budget policy, event timeline)
               + typed CampaignResult with paper-claim helpers
api          — the front door: run(spec_or_specs, seeds, engine) ->
               CampaignResult | SweepResult (solo vs batched dispatch)
provider     — cloud catalogs: capacity, spot pricing, preemption, NAT quirks
provisioner  — VMSS/InstanceGroups/SpotFleet-style group provisioning
budget       — CloudBank analogue: ledger, spend-rate, threshold alerts
overlay      — OSG CE + glideinWMS analogue: pilots, leases, matchmaking
simulator    — discrete-event cloud simulator binding the above
events       — typed, replayable CampaignTrace event stream (emitted
               byte-identically by every engine via collect="trace")
scenarios    — what-if spec library (spot mixes, outages, budgets)
sweep        — batched multi-campaign engine: B campaigns, one array program
elastic      — pod-pool -> mesh manager for synchronous SPMD training (TPU)
straggler    — speculative re-execution + slow-pod eviction

The CLI lives one level up: ``python -m repro.campaigns run spec.json``.
"""
from repro.core.api import run, sweep as run_sweep  # noqa: F401
from repro.core.budget import BudgetLedger  # noqa: F401
from repro.core.scenarios import default_suite  # noqa: F401
from repro.core.spec import (BudgetFloor, CampaignResult,  # noqa: F401
                             CampaignSpec, CapacityShift, CEOutage,
                             PriceCurve, PriceShift, SetTarget,
                             WorkloadCurve, paper_spec)
from repro.core.sweep import SweepResult  # noqa: F401
from repro.core.events import CampaignTrace, TraceRecorder  # noqa: F401
from repro.core.elastic import (ElasticRunner, GoodputReport,  # noqa: F401
                                PodPool, SimulatedElasticRunner,
                                drive_pool)
from repro.core.overlay import ComputeElement, Job, Pilot  # noqa: F401
from repro.core.provider import t4_catalog, tpu_catalog  # noqa: F401
from repro.core.provisioner import MultiCloudProvisioner  # noqa: F401
from repro.core.simulator import CloudSimulator, SimConfig  # noqa: F401
from repro.core.straggler import SpeculativeScheduler, StragglerMonitor  # noqa: F401
