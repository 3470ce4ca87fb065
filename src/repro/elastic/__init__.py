"""Elastic virtual-cluster subsystem (PR 2).

The paper's tenant *rents* VPSs to form the virtual MapReduce cluster —
this package makes the fleet mutable: leases and rental-cost accounting
(``leases``), a deterministic churn event model for failures / spot
preemptions / lease expiries (``churn``), autoscaler policies driven by
the PR 1 backlog counters (``autoscaler``), and the engine that glues
them to the discrete-event simulator (``engine``).
"""
from repro.elastic.autoscaler import (Autoscaler, BacklogThresholdScaler,
                                      CompactingScaler,
                                      CostCappedSpotScaler, FixedFleet,
                                      FleetObservation, ScaleDecision)
from repro.elastic.churn import ChurnConfig, ChurnEvent, ChurnModel
from repro.elastic.durability import (DurabilityConfig, DurabilityManager,
                                      DurabilitySubsystem,
                                      DurabilitySummary, RerepEvent)
from repro.elastic.engine import (ElasticActions, ElasticEngine,
                                  ElasticSubsystem, ElasticSummary)
from repro.elastic.leases import (ON_DEMAND, SPOT, Lease, LeaseBook,
                                  PriceSheet)
from repro.elastic.migration import (MigrationConfig, MigrationSubsystem,
                                     MigrationSummary)

__all__ = [
    "Autoscaler", "BacklogThresholdScaler", "CompactingScaler",
    "CostCappedSpotScaler", "FixedFleet", "FleetObservation",
    "ScaleDecision",
    "ChurnConfig", "ChurnEvent", "ChurnModel",
    "DurabilityConfig", "DurabilityManager", "DurabilitySubsystem",
    "DurabilitySummary", "RerepEvent",
    "ElasticActions", "ElasticEngine", "ElasticSubsystem",
    "ElasticSummary",
    "MigrationConfig", "MigrationSubsystem", "MigrationSummary",
    "ON_DEMAND", "SPOT", "Lease", "LeaseBook", "PriceSheet",
]
