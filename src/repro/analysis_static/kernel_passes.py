"""Kernel passes: static validation of the fused mega-kernel's
scalar-prefetched schedule (``kernels/fused_delta.py``).

The fused delta beat's correctness rests on a STATIC contract between
the work descriptor ``sdesc int32[N, 4] = (kind, owner, idx, gather)``
and the BlockSpec index maps: every pane tile / dirty slot / probe slot
is owned by exactly one schedule row, every gather index stays inside
its padded extent, the grid length equals the schedule length, and
every non-owning program's write window parks on the garbage tile so
each real output block has exactly one writer.  These passes re-derive
and verify that contract from the same builders the kernel ships
(``build_schedule`` / ``build_sdesc`` / ``make_out_specs``), evaluating
the REAL index maps against a concrete descriptor — a mutated schedule
(an off-by-one tile, a truncated grid, an out-of-range gather) is
caught before the first beat instead of silently double-writing a
block on device.
"""
from __future__ import annotations

from collections import Counter
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis_static.diagnostics import LintFinding
from repro.analysis_static import registry as R
from repro.analysis_static.registry import register_pass


def geometry_from_lowered(lowered, update_slots=None
                          ) -> Tuple[list, list]:
    """The fused grid geometry a delta beat over ``lowered`` would
    launch with (unsharded row extents): one ``ScanGeom`` per
    predicated scan stage, one ``JoinGeom`` per carried join (block
    joins arrive as single-bucket pseudo-partitions over the full PK
    pane)."""
    from repro.kernels.fused_delta import JoinGeom, ScanGeom, pane_tiling
    cat = lowered.plan.catalog
    sgeom, jgeom = [], []
    for st in lowered.scans:
        if not st.cols:
            continue
        R, nt = pane_tiling(cat.schemas[st.table].capacity)
        sgeom.append(ScanGeom(
            C=len(st.cols), Q=st.q_window, A=st.delta_words,
            R=R, nt=nt, D=cat.schemas[st.table].dirty_cap))
    for j in lowered.joins:
        if j.kind == "gather":
            continue
        if j.kind == "partitioned":
            B, P = j.bucket_cap, j.n_partitions
        else:
            B, P = cat.schemas[j.pk_table].capacity, 1
        jgeom.append(JoinGeom(B=B, D=cat.schemas[j.spine].dirty_cap,
                              P=P))
    return sgeom, jgeom


def synthesize_sdesc(sgeom, jgeom, schedule=None) -> np.ndarray:
    """A concrete descriptor for static validation: the real
    ``build_sdesc`` over worst-case in-range gathers (dirty rows at the
    far end of each padded extent, probes at the last bucket)."""
    from repro.kernels.fused_delta import build_schedule, build_sdesc
    if schedule is None:
        schedule = build_schedule(sgeom, jgeom)
    scan_rows = [np.full((g.D,), g.nt * g.R - 1, np.int32)
                 for g in sgeom]
    buckets = [np.full((g.D,), g.P - 1, np.int32) for g in jgeom]
    return np.asarray(build_sdesc(schedule, sgeom, jgeom, scan_rows,
                                  buckets))


@register_pass("fused-schedule", "kernel",
               (R.KERNEL_SCHEDULE_COVERAGE, R.KERNEL_GRID_LENGTH),
               "schedule covers every extent exactly once; grid length")
def lint_fused_schedule(sgeom, jgeom, schedule,
                        grid_len: Optional[int] = None,
                        location: str = "fused") -> List[LintFinding]:
    """Every pane tile, dirty slot and probe slot of every owner is
    covered by EXACTLY one schedule row, and the grid is exactly as
    long as the schedule."""
    out = []
    schedule = np.asarray(schedule)
    want_n = (sum(g.nt + g.D for g in sgeom)
              + sum(g.D for g in jgeom))
    if schedule.ndim != 2 or schedule.shape[1] < 3:
        return [LintFinding(
            R.KERNEL_GRID_LENGTH,
            f"schedule shape {schedule.shape} is not [N, >=3]",
            location=location)]
    if schedule.shape[0] != want_n:
        out.append(LintFinding(
            R.KERNEL_GRID_LENGTH,
            f"schedule has {schedule.shape[0]} rows but the geometry "
            f"demands {want_n} grid programs", location=location))
    if grid_len is not None and grid_len != schedule.shape[0]:
        out.append(LintFinding(
            R.KERNEL_GRID_LENGTH,
            f"grid length {grid_len} != schedule length "
            f"{schedule.shape[0]}", location=location))
    extents = {}                 # (kind, owner) -> extent
    from repro.kernels.fused_delta import _DIRTY, _PANE, _PROBE
    for s, g in enumerate(sgeom):
        extents[(_PANE, s)] = g.nt
        extents[(_DIRTY, s)] = g.D
    for j, g in enumerate(jgeom):
        extents[(_PROBE, j)] = g.D
    seen = Counter()
    for kind, owner, idx in schedule[:, :3]:
        key = (int(kind), int(owner))
        if key not in extents:
            out.append(LintFinding(
                R.KERNEL_SCHEDULE_COVERAGE,
                f"schedule row targets unknown (kind, owner) {key}",
                location=location))
            continue
        if not 0 <= int(idx) < extents[key]:
            out.append(LintFinding(
                R.KERNEL_SCHEDULE_COVERAGE,
                f"schedule row (kind {int(kind)}, owner {int(owner)}) "
                f"indexes {int(idx)} outside [0, {extents[key]})",
                location=location))
            continue
        seen[(key, int(idx))] += 1
    for key, extent in extents.items():
        for idx in range(extent):
            n = seen.get((key, idx), 0)
            if n != 1:
                out.append(LintFinding(
                    R.KERNEL_SCHEDULE_COVERAGE,
                    f"(kind {key[0]}, owner {key[1]}) unit {idx} is "
                    f"covered by {n} schedule rows (want exactly 1)",
                    location=location))
    return out


@register_pass("gather-bounds", "kernel", (R.KERNEL_GATHER_BOUNDS,),
               "scalar-prefetch gather indices in bounds")
def lint_gather_bounds(sgeom, jgeom, sdesc,
                       location: str = "fused") -> List[LintFinding]:
    """DIRTY gathers stay inside the padded pane extent (nt * R) and
    PROBE gathers inside the bucket count — the BlockSpec index maps
    DMA exactly these blocks, and an out-of-range index reads (or
    clamps onto) someone else's rows."""
    from repro.kernels.fused_delta import _DIRTY, _PROBE
    out = []
    sdesc = np.asarray(sdesc)
    if sdesc.ndim != 2 or sdesc.shape[1] != 4:
        return [LintFinding(
            R.KERNEL_GATHER_BOUNDS,
            f"descriptor shape {sdesc.shape} is not [N, 4]",
            location=location)]
    for kind, owner, idx, gather in sdesc:
        kind, owner, gather = int(kind), int(owner), int(gather)
        if kind == _DIRTY and 0 <= owner < len(sgeom):
            hi = sgeom[owner].nt * sgeom[owner].R
            if not 0 <= gather < hi:
                out.append(LintFinding(
                    R.KERNEL_GATHER_BOUNDS,
                    f"dirty gather {gather} of scan {owner} escapes "
                    f"[0, {hi})", location=location))
        elif kind == _PROBE and 0 <= owner < len(jgeom):
            if not 0 <= gather < jgeom[owner].P:
                out.append(LintFinding(
                    R.KERNEL_GATHER_BOUNDS,
                    f"probe bucket {gather} of join {owner} escapes "
                    f"[0, {jgeom[owner].P})", location=location))
    return out


def _eval_index_map(spec, i: np.ndarray, sdesc: np.ndarray
                    ) -> Tuple[np.ndarray, ...]:
    """Evaluate a BlockSpec's index map for every grid step at once
    (the maps are elementwise in ``i`` and read the descriptor in its
    flattened scalar-prefetch form)."""
    got = spec.index_map(i, sdesc.reshape(-1))
    return tuple(np.asarray(g) for g in got)


@register_pass("garbage-park", "kernel", (R.KERNEL_GARBAGE_PARK,),
               "non-owners park on the garbage tile; one writer/block")
def lint_garbage_park(sgeom, jgeom, sdesc,
                      location: str = "fused") -> List[LintFinding]:
    """Evaluate the SHIPPED output index maps against a concrete
    descriptor: every non-owning grid step must land on the garbage
    block (index ``nt`` for panes, ``D`` for dirty/probe slots), and
    every real block must have exactly one writer."""
    from repro.kernels.fused_delta import (_DIRTY, _PANE, _PROBE,
                                           make_out_specs)
    out = []
    sdesc = np.asarray(sdesc)
    N = sdesc.shape[0]
    i = np.arange(N)
    specs, _shapes = make_out_specs(sgeom, jgeom)
    owners, parks, extents, labels = [], [], [], []
    for s, g in enumerate(sgeom):
        owners.append((_PANE, s))
        parks.append(g.nt)
        extents.append(g.nt)
        labels.append(f"pane[{s}]")
        owners.append((_DIRTY, s))
        parks.append(g.D)
        extents.append(g.D)
        labels.append(f"dirty[{s}]")
    for j, g in enumerate(jgeom):
        owners.append((_PROBE, j))
        parks.append(g.D)
        extents.append(g.D)
        labels.append(f"probe[{j}]")
    for spec, (kind, owner), park, extent, label in zip(
            specs, owners, parks, extents, labels):
        blocks = _eval_index_map(spec, i, sdesc)[0]
        is_owner = (sdesc[:, 0] == kind) & (sdesc[:, 1] == owner)
        stray = np.flatnonzero(~is_owner & (blocks != park))
        if stray.size:
            out.append(LintFinding(
                R.KERNEL_GARBAGE_PARK,
                f"{stray.size} non-owning program(s) of {label} write "
                f"real blocks (e.g. step {int(stray[0])} -> block "
                f"{int(blocks[stray[0]])}, park is {park})",
                location=location))
        writes = Counter(int(b) for b in blocks[is_owner])
        multi = {b: n for b, n in writes.items() if n > 1 and b != park}
        if multi:
            out.append(LintFinding(
                R.KERNEL_GARBAGE_PARK,
                f"real output blocks of {label} with multiple writers: "
                f"{dict(sorted(multi.items()))}", location=location))
        escaped = [b for b in writes if not 0 <= b <= extent]
        if escaped:
            out.append(LintFinding(
                R.KERNEL_GARBAGE_PARK,
                f"owner writes of {label} escape [0, {extent}]: "
                f"{sorted(escaped)}", location=location))
    return out


def run_kernel_passes(lowered, update_slots=None,
                      location: str = "fused") -> List[LintFinding]:
    """The full kernel bundle for a plan's fused delta geometry."""
    from repro.kernels.fused_delta import build_schedule
    sgeom, jgeom = geometry_from_lowered(lowered, update_slots)
    if not sgeom and not jgeom:
        return []
    schedule = build_schedule(sgeom, jgeom)
    sdesc = synthesize_sdesc(sgeom, jgeom, schedule)
    return (lint_fused_schedule(sgeom, jgeom, schedule,
                                grid_len=schedule.shape[0],
                                location=location)
            + lint_gather_bounds(sgeom, jgeom, sdesc, location=location)
            + lint_garbage_park(sgeom, jgeom, sdesc, location=location))
