"""Multi-tenant placement control plane (service layer).

The layer between ``core.online.OnlinePlacer`` and the launch/serving
front ends:

  policy:       TenantConfig, weighted max-min shares (water-filling),
                FairSharePolicy drain scheduling, preemption-class rules
  controlplane: ControlPlane — per-tenant queues, fair admission into
                ``admit_many`` micro-batches, preemption, churn
                reconciliation, conservation ledger
  defrag:       atomic global re-optimization of the standing ticket set
  gossip:       GossipBus — push-gossip of versioned per-region share
                estimates (R * fanout messages per round)
  regions:      RegionalControlPlane — one recursive broker over R
                regions, each a region-local ControlPlane over a compacted
                subgraph (core.compact views: every solve sized n_r, not
                n) or, nested ``levels`` deep, a broker of the same class;
                coordinated only by gossip among siblings + one bounded
                2PC per spanning dataflow over its multi-hop region chain;
                constructed by ``ControlPlane(rg, regions=R)`` or
                ``ControlPlane(rg, levels=L, branching=b)``, bit-identical
                to the centralized plane at R = 1
"""
from .controlplane import ControlPlane, Request, TenantState  # noqa: F401
from .defrag import DefragResult, defrag, global_objective  # noqa: F401
from .gossip import GossipBus, ShareRecord  # noqa: F401
from .regions import (  # noqa: F401
    RegionalControlPlane,
    SpanPart,
    SpanningTicket,
    cut_edges,
    partition_regions,
    region_subgraph,
    resolve_nesting,
    split_dataflow,
    split_dataflow_chain,
    validate_region_of,
)
from .policy import (  # noqa: F401
    CLASS_BEST_EFFORT,
    CLASS_CRITICAL,
    CLASS_STANDARD,
    FairSharePolicy,
    TenantConfig,
    fairness_summary,
    maxmin_shares,
    may_preempt,
)
