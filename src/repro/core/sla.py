"""Bounded-computation model + SLA provisioning (paper §3.5).

SharedDB's key property: per-cycle work is a STATIC function of table
capacities and the query-slot capacity — never of the number of submitted
queries.  This module derives the worst-case cycle cost analytically from a
compiled plan and answers the paper's provisioning question: "if the SLA
says 3 seconds, provision so a worst-case cycle takes <= 1.5 s" (a query
waits at most one cycle and executes in the next).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

from repro.core.plan import CompiledPlan
from repro.roofline.analysis import TARGET_DEVICE_KIND, peaks


@dataclasses.dataclass(frozen=True)
class HwModel:
    flops_per_s: float               # per chip
    bytes_per_s: float               # HBM
    sort_const: float = 8.0          # comparisons per element per log2

    @classmethod
    def for_device(cls, device_kind: str = TARGET_DEVICE_KIND
                   ) -> "HwModel":
        """The published peaks of ``device_kind`` (``roofline.PEAKS``;
        an unknown kind raises)."""
        hw = peaks(device_kind)
        return cls(flops_per_s=hw["peak_flops"], bytes_per_s=hw["hbm_bw"])


def cycle_cost(plan: CompiledPlan, hw: Optional[HwModel] = None) -> Dict:
    """Worst-case per-cycle flops/bytes per plan node (single chip of
    ``hw``; the target part's peaks when omitted)."""
    hw = hw or HwModel.for_device()
    Q = plan.qcap
    W = Q // 32
    nodes = {}
    total_flops = total_bytes = 0.0
    for table, node in plan.scans.items():
        T = plan.catalog.schemas[table].capacity
        C = max(len(node.cols), 1)
        f = 4.0 * T * Q * C + 2.0 * T * Q          # compares + pack
        b = 4.0 * T * C + 4.0 * T * W
        nodes[f"scan:{table}"] = {"flops": f, "bytes": b}
        total_flops += f
        total_bytes += b
    for j in plan.joins:
        T = plan.catalog.schemas[j.spine].capacity
        f = 2.0 * T * W
        b = T * (8.0 + 8.0 * W)                    # fk+rid gather + masks
        nodes[f"join:{j.spine}->{j.pk_table}"] = {"flops": f, "bytes": b}
        total_flops += f
        total_bytes += b
    for s in plan.sorts:
        T = plan.catalog.schemas[s.spine].capacity
        f = hw.sort_const * T * max(math.log2(T), 1.0)
        b = 8.0 * T * (1 + W)
        nodes[f"sort:{s.spine}.{s.col}"] = {"flops": f, "bytes": b}
        total_flops += f
        total_bytes += b
    for g in plan.groups:
        T = plan.catalog.schemas[g.spine].capacity
        f = 4.0 * T * g.agg.n_groups * Q / 1024    # MXU contraction, tiled
        f = max(f, 4.0 * T * Q)                    # segment-sum floor
        b = 4.0 * T * (1 + W) + 8.0 * g.agg.n_groups * Q
        nodes[f"group:{g.spine}.{g.agg.group_col}"] = {"flops": f,
                                                       "bytes": b}
        total_flops += f
        total_bytes += b
    t_flops = total_flops / hw.flops_per_s
    t_bytes = total_bytes / hw.bytes_per_s
    return {"nodes": nodes, "total_flops": total_flops,
            "total_bytes": total_bytes,
            "worst_cycle_s": max(t_flops, t_bytes)}


def provision(plan: CompiledPlan, sla_seconds: float,
              hw: Optional[HwModel] = None) -> Dict:
    """Chips needed so worst-case latency (2 cycles) meets the SLA,
    assuming operator replication / partitioning scales linearly (§4.5)."""
    cost = cycle_cost(plan, hw)
    budget = sla_seconds / 2.0
    chips = max(1, math.ceil(cost["worst_cycle_s"] / budget))
    return {"worst_cycle_s": cost["worst_cycle_s"],
            "cycle_budget_s": budget,
            "chips_required": chips,
            "guarantee": f"p100 latency <= {sla_seconds}s at ANY "
                         f"concurrency <= {plan.qcap} queries/cycle"}
