"""Lowering: CompiledPlan -> explicit staged operator graph (the IR).

``compile_plan`` (plan.py) performs the paper's *logical* optimization:
predicate pushdown and operator merging across templates (Fig. 2/3).
This module performs the *physical* lowering: it turns the merged plan
into an explicit pipeline of stages

    update-apply -> shared scans -> shared joins
                 -> shared sorts / group-bys -> result routing

with every piece of static metadata — per-node word windows, subscriber
bitmasks, slot layouts, bounded union caps, per-query limit vectors —
computed HERE, at lowering time, instead of inside the traced closure.
The lowered graph is inspectable (``LoweredPlan.stages()``), and executing
it is a mechanical walk that delegates each hot loop to an operator
backend (backends.py): the jnp reference ops or the Pallas TPU kernels.

Join access paths are chosen at lowering time, per node, from table
capacities:

  * ``gather``      — the PK table maintains a dense key->row index
    (storage.py), so the shared PK-FK join is an O(1) gather per spine
    row.  This is the TPU-native replacement for the paper's hash join
    and needs no kernel; both backends share it.
  * ``partitioned`` — no dense index but a large table: the PK side is
    range-partitioned into fixed-capacity buckets once per heartbeat at
    update-apply time (storage.build_key_partitions) and each spine row
    probes exactly one bucket — O(Tl*Tr/P) instead of O(Tl*Tr)
    (kernels/partitioned_join.py on the Pallas backend).
  * ``block``       — no dense index and a small table
    (< PARTITIONED_MIN_CAPACITY rows): the dense blocked key-equality
    kernel fused with query-set intersection (kernels/bitmask_join.py);
    partitioning overhead is not worth it at this size.

Scan predicate binding is likewise precomputed: each ScanStage carries
static gather index arrays (``covered``, ``param_idx``) built ONCE here,
so the traced cycle binds a stage's whole lo/hi predicate matrix from the
packed admission buffers with one vectorized op — no per-template python
scatter loops on the hot path, regardless of template count.

Scans are also INCREMENTAL: ``build_cycle`` returns each predicated
stage's window-local bitmask words as a carry, and ``build_delta_cycle``
consumes that carry to re-evaluate only (changed admission word columns)
∪ (the update batch's dirty rows, storage.apply_updates) per heartbeat —
steady-state scan cost drops from O(rows × queries) to
O(rows × changed_slots + dirty × queries).  The executor picks the
flavour host-side per heartbeat and falls back to the full rescan when
the deltas overflow their fixed capacities.

JOINS are incremental too.  The heartbeat carry is widened from scan
words to (scan words, key partitions) plus the per-join rid arrays the
executor threads from ``results["_join_rids"]``: a JoinStage's rid
vector is a pure function of (the spine's fk column, the PK table's
keys/validity) — query admission only changes the MASKS, never the
rids — so on a heartbeat where the PK side was untouched, the carried
rids stay exact for every spine row outside the update batch's dirty
set.  ``build_delta_cycle(..., delta_joins=True)`` re-probes ONLY the
dirty spine rows (``backend.join_delta`` / kernels/fused_delta.py for
partitioned stages, a dense dirty-row probe for block stages) and merges
them into the carried rid array with the same sorted-scatter fast path
as delta scans.  The executor falls back to the full probe — within the
delta-scan cycle, via the ``delta_joins=False`` flavour — whenever a PK
table was written this heartbeat (its partitions rebuild, see
storage.refresh_key_partitions), a dirty set overflowed, or no rid
carry exists yet (first heartbeat / post-relayout), and the full-rescan
cycle reseeds BOTH carry halves.  The O(1) gather joins carry nothing:
the index gather is already cheaper than any merge.

Per-cycle work remains a static function of table/slot capacities — the
bounded-computation property (§3.5) — because every shape below is fixed
at lowering time.

The lowered stage graph is also the input to the MESH-AWARE lowering in
core/sharding.py: ``build_sharded_cycle`` / ``build_sharded_delta_cycle``
re-thread the same stages through a ``shard_map`` over a row mesh
(row-sharded spines and carries, replicated probe sides), reusing this
module's predicate binding and post-scan verbatim — a 1-shard mesh is
bit-identical to the cycles built here.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import operators as ops
from repro.core.backends import OperatorBackend
from repro.core.plan import CompiledPlan, GroupAgg

INT_MIN = ops.INT_MIN
INT_MAX = ops.INT_MAX

# join access-path thresholds: an index-less PK table below the minimum
# capacity runs the dense block kernel; at or above it, the bucketed
# partitioned probe (bucket capacity targets one lane-friendly tile)
PARTITIONED_MIN_CAPACITY = 512
PARTITION_BUCKET_CAP = 256

# incremental scans: each stage's admission pane covers a CONTIGUOUS
# range of window_words / DELTA_PANE_DIVISOR words (min 1).  The pane is
# a static shape, paid on every delta heartbeat, so it trades
# steady-state cost against how much admission churn still qualifies for
# the delta path; a contiguous range (rather than scattered words) keeps
# the merge an in-place dynamic_update_slice on the donated carry —
# scatter-style merges cost as much as the full compare on small tables.
DELTA_PANE_DIVISOR = 8

# (template, q_offset_in_window, slot_capacity)
SlotRange = Tuple[str, int, int]


def _round_up_128(x: int) -> int:
    return ((max(1, x) + 127) // 128) * 128


def partition_layout(capacity: int,
                     stats: Optional[Dict[str, int]] = None
                     ) -> Tuple[int, int]:
    """(n_partitions, bucket_cap) for a PK table of this capacity.

    With measured key ``stats`` — {"n_live": valid rows, "max_dup":
    widest duplicate-key run}, recorded from the initial snapshot at
    engine-construction time — the bucket capacity adapts to real
    occupancy instead of the static PARTITION_BUCKET_CAP heuristic: a
    sparsely loaded table gets narrower buckets (a cheaper probe pane
    for the delta/fused kernels, whose work per dirty row is O(B)), and
    a duplicate-heavy key column gets buckets at least as wide as its
    widest run.  Correctness never depends on the layout — a key run
    spanning buckets p..q resolves to bucket q, which holds the run's
    tail (the max row id), for ANY bucket_cap — so stats steer only the
    probe pane width, rounded to a 128-lane multiple.  Stats are
    measured once and baked into the JoinStage: shapes stay static, the
    bounded-computation property holds.
    """
    if stats is None:
        bucket_cap = min(PARTITION_BUCKET_CAP, capacity)
        return -(-capacity // bucket_cap), bucket_cap
    occupancy = min(1.0, max(0, int(stats.get("n_live", capacity)))
                    / capacity)
    target = _round_up_128(int(PARTITION_BUCKET_CAP * occupancy))
    bucket_cap = min(capacity,
                     max(target, _round_up_128(int(stats.get("max_dup",
                                                             1)))))
    return -(-capacity // bucket_cap), bucket_cap


# ---------------------------------------------------------------------------
# Stage IR
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ScanStage:
    """One ClockScan pass over a base table for ALL referencing queries.

    The predicate scatter plan is precomputed at lowering time: given the
    packed admission buffers (params int32[qcap, P_max, 2], active
    bool[qcap]), the stage's whole lo/hi predicate matrix binds with one
    vectorized gather — ``covered`` marks window slots belonging to a
    referencing template, ``param_idx`` maps (predicated column, window
    slot) to the packed parameter row (-1 = unbound -> pass-all when
    active).

    ``delta_words`` is the stage's admission-pane capacity on the
    incremental path (``build_delta_cycle``): the CONTIGUOUS range of
    window words whose slots may change admission between consecutive
    heartbeats and still take the delta scan.  The pane recomputes
    exactly that many adjacent word columns over all rows, so a smaller
    capacity means a cheaper steady-state heartbeat but an earlier
    fallback to the full rescan — the executor checks the changed span
    host-side before dispatch.
    """
    table: str
    cols: Tuple[str, ...]
    wlo: int                                  # word window [wlo, whi)
    whi: int
    slots: Tuple[SlotRange, ...]              # referencing templates
    covered: np.ndarray                       # bool[q_window]
    param_idx: np.ndarray                     # int32[max(C,1), q_window]
    delta_words: int = 1                      # admission-pane word cap

    @property
    def q_window(self) -> int:
        return (self.whi - self.wlo) * 32


@dataclasses.dataclass(frozen=True)
class JoinStage:
    """One shared PK-FK join per (spine, fk, pk) signature.

    Non-``gather`` stages are DELTA-ELIGIBLE: their rid vector depends
    only on the spine's fk column and the PK table's snapshot — not on
    admission — so the executor carries it across heartbeats and
    ``build_delta_cycle(delta_joins=True)`` re-probes just the dirty
    spine rows, falling back to the full probe when the PK side was
    written (partitions rebuilt) or the dirty set overflowed.
    """
    spine: str
    fk_col: str
    pk_table: str
    kind: str                                 # "gather"|"partitioned"|"block"
    pk_col: str                               # key column on the PK side
    sub_mask: np.ndarray                      # uint32[W] subscriber words
    n_partitions: int = 0                     # partitioned kind only
    bucket_cap: int = 0

    @property
    def key(self) -> Tuple[str, str, str]:
        """The stage's identity in ``results["_join_rids"]`` / rid carry."""
        return (self.spine, self.fk_col, self.pk_table)

    @property
    def label(self) -> str:
        """The stage's name in its scope, ``<spine>.<fk>><pk table>``."""
        return f"{self.spine}.{self.fk_col}>{self.pk_table}"


@dataclasses.dataclass(frozen=True)
class SortStage:
    """Shared sort over the bounded union + fused per-query top-n."""
    spine: str
    col: str
    desc: bool
    wlo: int
    whi: int
    sub_mask: np.ndarray                      # uint32[whi-wlo], window-local
    union_cap: int
    slots: Tuple[SlotRange, ...]


@dataclasses.dataclass(frozen=True)
class GroupStage:
    """Shared group-by: phase 1 over the union, phase 2 per query."""
    spine: str
    agg: GroupAgg
    wlo: int
    whi: int
    union_cap: int
    slots: Tuple[SlotRange, ...]


@dataclasses.dataclass(frozen=True)
class RouteStage:
    """Natural-order routing for unsorted templates, one pass per spine."""
    spine: str
    wlo: int
    whi: int
    sub_mask: np.ndarray                      # uint32[whi-wlo], window-local
    union_cap: int
    slots: Tuple[SlotRange, ...]


@dataclasses.dataclass(frozen=True)
class LoweredPlan:
    plan: CompiledPlan
    qcap: int
    W: int
    n_params_max: int                         # packed params depth P_max
    scans: Tuple[ScanStage, ...]
    joins: Tuple[JoinStage, ...]
    sorts: Tuple[SortStage, ...]
    groups: Tuple[GroupStage, ...]
    routes: Tuple[RouteStage, ...]
    limits: np.ndarray                        # int32[qcap] per-slot top-n

    def stages(self) -> Iterator[Tuple[str, object]]:
        """The staged execution order (the IR, for inspection/debug)."""
        for s in self.scans:
            yield "scan", s
        for j in self.joins:
            yield "join", j
        for s in self.sorts:
            yield "sort", s
        for g in self.groups:
            yield "group", g
        for r in self.routes:
            yield "route", r


# ---------------------------------------------------------------------------
# Lowering
# ---------------------------------------------------------------------------


def _slot_ranges(plan: CompiledPlan, names: List[str],
                 base: int) -> Tuple[SlotRange, ...]:
    return tuple((n, plan.offsets[n] - base, plan.caps[n]) for n in names)


def lower_plan(plan: CompiledPlan,
               key_stats: Optional[Dict[str, Dict[str, int]]] = None
               ) -> LoweredPlan:
    """Lower the compiled plan to the staged IR.

    ``key_stats`` optionally maps PK table name -> measured key skew
    ({"n_live", "max_dup"}, see ``partition_layout``) so partitioned
    joins adapt their bucket layout to real occupancy; the executor
    measures it from the initial snapshot.  ``None`` keeps the static
    layout (runtime relayout paths that have no snapshot in hand).
    """
    cat = plan.catalog
    W = plan.qcap // 32

    scans = []
    for table, node in plan.scans.items():
        wlo, whi = plan.word_range(node.referencing)
        base = wlo * 32
        q_sub = (whi - wlo) * 32
        # lowering-time predicate scatter plan: static gather indices into
        # the packed admission buffers (no python loops in the cycle)
        covered = np.zeros(q_sub, bool)
        for name in node.referencing:
            o = plan.offsets[name] - base
            covered[o:o + plan.caps[name]] = True
        param_idx = np.full((max(len(node.cols), 1), q_sub), -1, np.int32)
        for name, col_idx, pidx in node.bindings:
            o = plan.offsets[name] - base
            param_idx[col_idx, o:o + plan.caps[name]] = pidx
        scans.append(ScanStage(
            table=table, cols=tuple(node.cols), wlo=wlo, whi=whi,
            slots=_slot_ranges(plan, node.referencing, base),
            covered=covered, param_idx=param_idx,
            delta_words=max(1, (whi - wlo) // DELTA_PANE_DIVISOR)))

    joins = []
    for j in plan.joins:
        schema = cat.schemas[j.pk_table]
        if schema.pk is None:
            raise ValueError(
                f"join {j.spine}->{j.pk_table}: PK table has no key column")
        n_parts, bucket_cap = 0, 0
        if schema.key_space > 0:
            kind = "gather"
        elif schema.capacity >= PARTITIONED_MIN_CAPACITY:
            kind = "partitioned"
            n_parts, bucket_cap = partition_layout(
                schema.capacity,
                None if key_stats is None else key_stats.get(j.pk_table))
        else:
            kind = "block"
        joins.append(JoinStage(
            spine=j.spine, fk_col=j.fk_col, pk_table=j.pk_table,
            kind=kind, pk_col=schema.pk,
            sub_mask=plan.sub_mask(j.subscribers),
            n_partitions=n_parts, bucket_cap=bucket_cap))

    sorts = []
    for s in plan.sorts:
        wlo, whi = plan.word_range(s.subscribers)
        T = cat.schemas[s.spine].capacity
        sorts.append(SortStage(
            spine=s.spine, col=s.col, desc=s.desc, wlo=wlo, whi=whi,
            sub_mask=plan.sub_mask(s.subscribers)[wlo:whi],
            union_cap=min(T, plan.union_cap),
            slots=_slot_ranges(plan, s.subscribers, wlo * 32)))

    groups = []
    for g in plan.groups:
        wlo, whi = plan.word_range(g.subscribers)
        T = cat.schemas[g.spine].capacity
        groups.append(GroupStage(
            spine=g.spine, agg=g.agg, wlo=wlo, whi=whi,
            union_cap=min(T, plan.group_union_cap),
            slots=_slot_ranges(plan, g.subscribers, wlo * 32)))

    routed = {name for st in sorts + groups for name, _, _ in st.slots}
    by_spine: Dict[str, List[str]] = {}
    for name, t in plan.templates.items():
        if name not in routed:
            by_spine.setdefault(t.spine, []).append(name)
    routes = []
    for spine, names in by_spine.items():
        wlo, whi = plan.word_range(names)
        T = cat.schemas[spine].capacity
        routes.append(RouteStage(
            spine=spine, wlo=wlo, whi=whi,
            sub_mask=plan.sub_mask(names)[wlo:whi],
            union_cap=min(T, plan.union_cap),
            slots=_slot_ranges(plan, names, wlo * 32)))

    limits = np.ones(plan.qcap, np.int32)
    for name, t in plan.templates.items():
        o, c = plan.offsets[name], plan.caps[name]
        limits[o:o + c] = min(t.limit, plan.max_results)

    return LoweredPlan(
        plan=plan, qcap=plan.qcap, W=W, n_params_max=plan.n_params_max,
        scans=tuple(scans), joins=tuple(joins), sorts=tuple(sorts),
        groups=tuple(groups), routes=tuple(routes), limits=limits)


def check_extension_prefix(old: LoweredPlan, new: LoweredPlan) -> None:
    """Validate that ``new`` prefix-stably EXTENDS ``old`` at the stage
    level — the IR contract dynamic plan folding (core/folding.py) rests
    on.

    Appending templates to a plan reuses the same static schedule
    builder (``lower_plan``), and because templates are appended AFTER
    every existing one, every node-dedup dict above re-encounters its
    old keys in the old order before any new key: existing stages keep
    their position, scan windows only widen on the high side (new
    templates own higher slot ranges), predicated column lists only
    append, and join stages keep their access path (same catalog + same
    key stats).  The actual derivation checks live in the planlint pass
    ``analysis_static.ir_passes.lint_extension_prefix`` (rule
    ``fold-prefix-stability``) — this entry point is kept so folding and
    tests keep one import path, and raises ``ValueError`` as before.
    """
    from repro.analysis_static.diagnostics import raise_on_error
    from repro.analysis_static.ir_passes import lint_extension_prefix
    raise_on_error(lint_extension_prefix(old, new), exc=ValueError)


# ---------------------------------------------------------------------------
# Executing the lowered graph: one heartbeat of the always-on plan
# ---------------------------------------------------------------------------
#
# Two cycle flavours share everything but the scan phase (the delta
# flavour additionally comes in two JOIN variants):
#
#   build_cycle        — full rescan: every scan re-evaluates the whole
#                        table (the bounded worst case, and the seeding
#                        cycle for the carried scan state).
#   build_delta_cycle  — incremental: each predicated scan re-evaluates
#                        only (changed admission word columns) ∪ (the
#                        update batch's dirty rows) against the PREVIOUS
#                        heartbeat's carried bitmask words.  With
#                        ``delta_joins=True`` the non-gather joins also
#                        re-probe only the dirty spine rows against the
#                        previous heartbeat's carried rid arrays.
#
# Both return ``carry = {"scan": {table: words}, "parts": {table:
# partitions}}`` so the executor can thread it into the next heartbeat;
# the rid half of the widened carry travels through
# ``results["_join_rids"]`` (distinct buffers from the donated carry, so
# pipelined in-flight results never alias a later dispatch's donation).
#
# Every stage traces under a named scope ``<stage>/<detail>``
# (``stage_scope``): the stage is one of ``STAGES`` and the detail its
# table, join or sort key.  Scopes are metadata only — they change no op
# and no fusion — but XLA keeps them in each compiled instruction's
# ``op_name``, so ``stage_map`` can name the stage of every op of a
# device trace.

#: the heartbeat's stages, in execution order
STAGES = ("apply", "partition", "scan", "fused_delta", "join", "sort",
          "group", "route")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$", re.MULTILINE)
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_TUPLE = re.compile(r"\btuple\(")


def stage_scope(stage: str, detail: str):
    """The named scope one heartbeat stage traces under."""
    return jax.named_scope(f"{stage}/{detail}")


def _scope_of(op_name: str) -> Optional[str]:
    """``"<stage>/<detail>"`` of an ``op_name``, else None."""
    parts = op_name.split("/")
    # the scope's two elements, then at least the primitive's name
    for i in range(len(parts) - 2):
        if parts[i] in STAGES:
            return f"{parts[i]}/{parts[i + 1]}"
    return None


def stage_map(hlo_text: str) -> Dict[str, str]:
    """{HLO instruction name: ``"<stage>/<detail>"``} of one compiled
    module's text (``compiled.as_text()``).

    An instruction's stage is read from its ``op_name`` metadata.  The
    compiler makes instructions of its own — copies, the pieces it splits
    a cumulative sum into, layout fusions — that carry no ``op_name`` or
    only a primitive's; each such one takes the stage of its first
    operand that has one (the text lists instructions in dependency
    order), else of its first user that has one.  Tuples, which only
    gather values, take none; instructions still without a stage are
    left out."""
    operands: Dict[str, List[str]] = {}     # in the text's order
    stage: Dict[str, str] = {}
    for m in _INSTRUCTION.finditer(hlo_text):
        name, rest = m.group(1), m.group(2)
        op = _OP_NAME.search(rest)
        scope = _scope_of(op.group(1)) if op else None
        if scope is not None:
            stage[name] = scope
        operands[name] = [] if _TUPLE.search(rest) else \
            _OPERAND.findall(rest.split("), ", 1)[0])
    users: Dict[str, List[str]] = {}
    for name, ops in operands.items():
        for o in ops:
            users.setdefault(o, []).append(name)
        if name not in stage:
            first = next((stage[o] for o in ops if o in stage), None)
            if first is not None:
                stage[name] = first
    for name in reversed(list(operands)):
        if name not in stage:
            first = next((stage[u] for u in users.get(name, ())
                          if u in stage), None)
            if first is not None:
                stage[name] = first
    return stage


def _build_apply_phase(lowered: LoweredPlan):
    """Update-apply + partition refresh (step 1, shared by all cycles)."""
    from repro.core.storage import (apply_updates, build_key_partitions,
                                    refresh_key_partitions)

    cat = lowered.plan.catalog
    # PK tables probed by partitioned joins: partition once per heartbeat,
    # shared by every join into the same table
    part_specs = {}
    for j in lowered.joins:
        if j.kind == "partitioned":
            part_specs.setdefault(
                j.pk_table, (j.pk_col, j.n_partitions, j.bucket_cap))

    def apply_phase(storage, updates, prev_parts=None):
        # apply updates in arrival order (cycle-consistent snapshot),
        # then refresh the partitioned joins' bucket structures from the
        # fresh snapshot (update-apply time, paper §4.4 access paths).
        # With a carried ``prev_parts`` (the delta cycles) a table whose
        # batch touched nothing keeps its partitions — rebuilding an
        # untouched table is idempotent, so skipping the sort is exact —
        # and ``rebuilt`` records which tables actually re-sorted.
        storage = dict(storage)
        for table, batch in updates.items():
            with stage_scope("apply", table):
                storage[table] = apply_updates(cat.schemas[table],
                                               storage[table], batch)
        partitions, rebuilt = {}, {}
        for table, (pk_col, n_parts, bucket_cap) in part_specs.items():
            t = storage[table]
            with stage_scope("partition", table):
                if prev_parts is None:
                    partitions[table] = build_key_partitions(
                        t[pk_col], t["_valid"], n_parts, bucket_cap)
                    rebuilt[table] = jnp.ones((), bool)
                else:
                    partitions[table], rebuilt[table] = \
                        refresh_key_partitions(t, pk_col, n_parts,
                                               bucket_cap, prev_parts[table])
        return storage, partitions, rebuilt

    return apply_phase


def _pane_window(st: ScanStage, covered, changed):
    """One stage's admission-pane geometry, host-free: (span, w0, over).

    ``span`` is the contiguous changed-word span over the stage's
    covered slots (0 = no admission change), ``w0`` the pane's first
    word column clamped so the static-width pane stays in range, and
    ``over`` the words by which the span exceeds the pane capacity
    (positive only on ineligible beats the executor should never have
    dispatched — the defensive invariant).
    """
    base = st.wlo * 32
    w = st.whi - st.wlo
    A = st.delta_words
    qd = changed[base:base + st.q_window] & covered
    wch = jnp.any(qd.reshape(w, 32), axis=1)
    first = jnp.argmax(wch).astype(jnp.int32)
    last = (w - 1 - jnp.argmax(wch[::-1])).astype(jnp.int32)
    span = jnp.where(jnp.any(wch), last - first + 1, 0)
    over = jnp.maximum(span - A, 0)
    w0 = jnp.minimum(first, w - A)
    return span, w0, over


def _pseudo_partitions(pk_tbl, pk_col: str):
    """A block join's PK side as a single-bucket partition structure.

    The whole key column is one bucket pane with bound INT_MIN (every
    probe routes to it), invalid rows padded with the key sentinel and
    row id -1 — exactly the ``storage.build_key_partitions`` encoding,
    so the one-bucket probe (max valid row with an equal key) matches
    ``storage.locate_rows_by_key`` bit for bit.  This funnels block-kind
    carried joins through the same fused/partitioned dirty-probe path
    instead of keeping a separate dense compare alive.
    """
    from repro.core.storage import INT_SENTINEL

    keys = pk_tbl[pk_col]
    valid = pk_tbl["_valid"]
    bkeys = jnp.where(valid, keys, INT_SENTINEL)[None, :]
    brows = jnp.where(valid, jnp.arange(keys.shape[0], dtype=jnp.int32),
                      -1)[None, :]
    bounds = jnp.full((1,), INT_MIN, jnp.int32)
    return bkeys, brows, bounds


def _bind_predicates(st: ScanStage, covered, pidx, queries):
    """One stage's (qok, lo, hi) from the packed admission buffers.

    The whole lo/hi predicate matrix binds in one vectorized gather —
    the scatter plan (covered, param_idx) is precomputed at lowering
    time, so there are no per-template python loops on the hot path.
    """
    base = st.wlo * 32
    act = queries["active"][base:base + st.q_window]
    qok = act & covered                          # admitted subscribers
    p = queries["params"][base:base + st.q_window]
    bound = pidx >= 0
    safe = jnp.maximum(pidx, 0)
    qs = jnp.arange(st.q_window)
    p_lo = p[qs[None, :], safe, 0]               # [C, q_window]
    p_hi = p[qs[None, :], safe, 1]
    lo = jnp.where(qok[None, :],
                   jnp.where(bound, p_lo, INT_MIN), INT_MAX)
    hi = jnp.where(qok[None, :],
                   jnp.where(bound, p_hi, INT_MAX), INT_MIN)
    return qok, lo, hi


def build_cycle(lowered: LoweredPlan, backend: OperatorBackend):
    """Returns cycle(storage, queries, updates) -> (storage', carry,
    results).

    queries: the packed admission batch —
             {"params": int32[qcap, P_max, 2], "active": bool[qcap]}
             (ONE host->device transfer per buffer per heartbeat; each
             template's slot range is a static view into it)
    updates: {table: update batch dict (see storage.empty_update_batch)}
    carry:   {"scan": {table: uint32[T, whi-wlo]} window-local scan words
             of every predicated stage, "parts": {table: key partitions
             of every partitioned-join PK table}} — the state
             ``build_delta_cycle`` consumes next heartbeat.
    results: per template row-id matrices / group top-k; all fixed shapes,
    plus "_overflow" (union-cap overflow count), "_join_rids" (whose
    arrays the executor threads forward as the rid half of the widened
    carry) and "_parts_rebuilt" (which PK tables re-sorted this beat).
    """
    from repro.core import dataquery as dq

    W = lowered.W
    apply_phase = _build_apply_phase(lowered)
    post_scan = _build_post_scan(lowered, backend)
    # lowering-time predicate scatter plans as device constants
    scan_covered = [jnp.asarray(s.covered) for s in lowered.scans]
    scan_pidx = [jnp.asarray(s.param_idx) for s in lowered.scans]

    def cycle(storage, queries, updates):
        storage, partitions, rebuilt = apply_phase(storage, updates)

        # shared scans (ClockScan): one pass per table for ALL queries,
        # each touching only its subscribers' word window.
        scan_masks, scan_carry = {}, {}
        for st, covered, pidx in zip(lowered.scans, scan_covered,
                                     scan_pidx):
            tbl = storage[st.table]
            with stage_scope("scan", st.table):
                if not st.cols:
                    # no predicated columns: the scan degenerates to
                    # valid-row x active-subscriber — skip the compare
                    # kernel
                    base = st.wlo * 32
                    act = queries["active"][base:base + st.q_window]
                    m = dq.pack(tbl["_valid"][:, None]
                                & (act & covered)[None])
                else:
                    _, lo, hi = _bind_predicates(st, covered, pidx, queries)
                    cols = jnp.stack([tbl[c] for c in st.cols])
                    m = backend.scan(cols, lo, hi, tbl["_valid"])
                    scan_carry[st.table] = m
                scan_masks[st.table] = jnp.pad(m, ((0, 0),
                                                   (st.wlo, W - st.whi)))

        carry = {"scan": scan_carry, "parts": partitions}
        results = post_scan(storage, partitions, scan_masks)
        results["_parts_rebuilt"] = rebuilt
        return storage, carry, results

    return cycle


def build_delta_cycle(lowered: LoweredPlan, backend: OperatorBackend,
                      delta_joins: bool = False):
    """Returns the incremental heartbeat:
    cycle(storage, carry, queries, updates) -> (storage', carry',
    results), or — with ``delta_joins=True`` —
    cycle(storage, carry, rid_carry, queries, updates).

    ``carry`` is the previous heartbeat's ``{"scan": window-local scan
    words, "parts": key partitions}`` (the ``build_cycle`` carry).
    ``queries`` additionally holds "changed": bool[qcap], true for slots
    whose (active, params) differ from the previously DISPATCHED
    heartbeat (computed host-side by the executor).  Each predicated
    scan then refreshes only

      * the admission pane — the contiguous ``st.delta_words``-word
        range containing every changed slot, recomputed over ALL rows
        with the regular compare kernel at pane width
        (32 * delta_words ≪ q_window) and merged with one in-place
        dynamic_update_slice on the donated carry, and
      * the dirty rows — the update batch's sorted/unique
        ``_dirty_rows`` re-evaluated against the FULL window via
        ``backend.scan_delta`` and scattered back by row on the
        sorted-unique fast path,

    and carries every other (row, word) pair forward verbatim.  Key
    partitions refresh the same way: a PK table whose batch touched
    nothing keeps its carried buckets (storage.refresh_key_partitions).

    With ``delta_joins=True``, ``rid_carry`` is the previous heartbeat's
    ``results["_join_rids"]`` and every non-gather JoinStage re-probes
    ONLY its spine's dirty rows (``backend.join_delta`` for partitioned
    stages, a dirty-row key-equality probe for block stages), merging
    the fresh rids into the carried array with the same sorted-scatter
    fast path.  The executor only dispatches this variant when NO
    carried stage's PK table was touched this heartbeat, so every
    carried rid was probed against partitions identical to this
    snapshot's.

    The executor guarantees eligibility host-side (the changed-word SPAN
    fits the pane, distinct dirty rows fit every table's set);
    ``results["_delta_overflow"]`` counts violations as a defensive
    invariant (0 on every eligible heartbeat).

    Correctness: a carried (row, slot) scan bit has an unchanged row
    (not dirty), unchanged slot binding (not changed), and an unchanged
    snapshot outside the dirty set — so its previous word is exactly
    what the full rescan would recompute.  A carried join rid is a pure
    function of (fk value, PK snapshot), BOTH unchanged for non-dirty
    spine rows on a PK-untouched heartbeat — admission changes never
    invalidate rids, they only change the masks, which are recomputed
    from the merged scan words every heartbeat.
    """
    from repro.core import dataquery as dq
    from repro.core.backends import FusedJoinIn, FusedScanIn
    from repro.core.storage import scatter_dirty_rows

    plan = lowered.plan
    cat = plan.catalog
    W = lowered.W
    apply_phase = _build_apply_phase(lowered)
    post_scan = _build_post_scan(lowered, backend)
    scan_covered = [jnp.asarray(s.covered) for s in lowered.scans]
    scan_pidx = [jnp.asarray(s.param_idx) for s in lowered.scans]
    carried_joins = [j for j in lowered.joins if j.kind != "gather"]
    carried_spines = sorted({j.spine for j in carried_joins})
    # the fused path: every predicated stage's pane + dirty rescan and
    # (with delta_joins) every carried join's dirty probe collapse into
    # ONE backend op; a backend without it keeps the chained ops
    fused = backend.fused_delta is not None

    def cycle(storage, carry, rid_carry, queries, updates):
        storage, partitions, rebuilt = apply_phase(storage, updates,
                                                   carry["parts"])
        changed = queries["changed"]

        scan_masks, new_carry = {}, {}
        delta_over = jnp.zeros((), jnp.int32)
        fused_scan_in, fused_stages = [], []
        for st, covered, pidx in zip(lowered.scans, scan_covered,
                                     scan_pidx):
            # a predicated stage's inputs to the fused op belong to it;
            # the chained ops and degenerate scans are the scan stage
            stage = "fused_delta" if fused and st.cols else "scan"
            with stage_scope(stage, st.table):
                tbl = storage[st.table]
                base = st.wlo * 32
                if not st.cols:
                    # degenerate scans are O(T*w) bit ops — cheaper to
                    # recompute than to track, so they carry no state (and
                    # stay outside the fused op)
                    act = queries["active"][base:base + st.q_window]
                    m = dq.pack(tbl["_valid"][:, None] & (act & covered)[None])
                    scan_masks[st.table] = jnp.pad(m, ((0, 0),
                                                       (st.wlo, W - st.whi)))
                    continue
                _, lo, hi = _bind_predicates(st, covered, pidx, queries)
                cols = jnp.stack([tbl[c] for c in st.cols])
                A = st.delta_words

                # admission pane: the contiguous word range holding every
                # changed slot (recomputed over all rows at pane width) and
                # the dirty rows (rescanned at full window width); both
                # merge in place into the donated carry — fused in one op,
                # or chained through scan / scan_delta / the scatter
                span, w0, over = _pane_window(st, covered, changed)
                delta_over += over
                lo_a = jax.lax.dynamic_slice(lo, (0, w0 * 32),
                                             (lo.shape[0], A * 32))
                hi_a = jax.lax.dynamic_slice(hi, (0, w0 * 32),
                                             (hi.shape[0], A * 32))
                dr = tbl["_dirty_rows"]
                delta_over += tbl["_dirty_overflow"].astype(jnp.int32)
                if fused:
                    fused_scan_in.append(FusedScanIn(
                        cols=cols, lo=lo, hi=hi, lo_p=lo_a, hi_p=hi_a,
                        valid=tbl["_valid"], carry=carry["scan"][st.table],
                        w0=w0, span=span, rows=dr,
                        dn=tbl["_dirty_n"].astype(jnp.int32)))
                    fused_stages.append(st)
                    continue
                pane = backend.scan(cols, lo_a, hi_a, tbl["_valid"])
                m = jax.lax.dynamic_update_slice(carry["scan"][st.table],
                                                 pane, (0, w0))
                dwords = backend.scan_delta(cols, lo, hi, tbl["_valid"], dr)
                m = scatter_dirty_rows(m, dr, dwords,
                                       cat.schemas[st.table].capacity)
                new_carry[st.table] = m
                scan_masks[st.table] = jnp.pad(m, ((0, 0),
                                                   (st.wlo, W - st.whi)))

        fused_join_in = []
        if delta_joins:
            # defensive: a carried join's spine dirty set must not have
            # overflowed either (the host checks the same thing exactly)
            for spine in carried_spines:
                with stage_scope("join", spine):
                    delta_over += \
                        storage[spine]["_dirty_overflow"].astype(jnp.int32)
            if fused:
                for j in carried_joins:
                    tbl = storage[j.spine]
                    with stage_scope("fused_delta", j.label):
                        if j.kind == "partitioned":
                            bkeys, brows, bounds = partitions[j.pk_table]
                        else:  # block: single-bucket pseudo-partitions
                            bkeys, brows, bounds = _pseudo_partitions(
                                storage[j.pk_table], j.pk_col)
                        fused_join_in.append(FusedJoinIn(
                            keys=tbl[j.fk_col], rows=tbl["_dirty_rows"],
                            dn=tbl["_dirty_n"].astype(jnp.int32),
                            bkeys=bkeys, brows=brows, bounds=bounds,
                            rid_carry=rid_carry[j.key]))

        fused_rids = None
        if fused and (fused_scan_in or fused_join_in):
            with stage_scope("fused_delta", "op"):
                words, rids = backend.fused_delta(tuple(fused_scan_in),
                                                  tuple(fused_join_in))
                for st, m in zip(fused_stages, words):
                    new_carry[st.table] = m
                    scan_masks[st.table] = jnp.pad(
                        m, ((0, 0), (st.wlo, W - st.whi)))
            if delta_joins:
                fused_rids = {j.key: r
                              for j, r in zip(carried_joins, rids)}

        results = post_scan(storage, partitions, scan_masks,
                            rid_carry=rid_carry, fused_rids=fused_rids)
        results["_delta_overflow"] = delta_over
        results["_parts_rebuilt"] = rebuilt
        return storage, {"scan": new_carry, "parts": partitions}, results

    if delta_joins:
        return cycle
    # full-probe variant: same signature minus the rid carry
    return lambda storage, carry, queries, updates: cycle(
        storage, carry, None, queries, updates)


def _build_post_scan(lowered: LoweredPlan, backend: OperatorBackend):
    """Joins, sorts, group-bys and routing (steps 3-6, shared by all
    cycle flavours; ``rid_carry`` switches the joins to the delta
    probe)."""
    from repro.core.storage import locate_rows_by_key, scatter_dirty_rows

    plan = lowered.plan
    cat = plan.catalog
    limits = jnp.asarray(lowered.limits)
    join_subs = [jnp.asarray(j.sub_mask) for j in lowered.joins]
    sort_subs = [jnp.asarray(s.sub_mask) for s in lowered.sorts]
    route_subs = [jnp.asarray(r.sub_mask) for r in lowered.routes]

    def post_scan(storage, partitions, scan_masks, rid_carry=None,
                  fused_rids=None):
        # 3. shared joins: ONE big join per signature, query_id in the
        #    predicate via bitmask intersection; non-subscribers pass
        #    through untouched.  With a carried rid array (delta-join
        #    heartbeats) the probe shrinks to the spine's dirty rows:
        #    fresh rids merge into the carry on the sorted-scatter fast
        #    path and the bitmask intersection — which DOES depend on
        #    this heartbeat's admission — is recomputed from the merged
        #    scan words as usual.  With ``fused_rids`` the fused delta
        #    op already merged every carried join's rids; only the
        #    intersection remains here.
        spine_masks = dict(scan_masks)
        join_rids = {}
        for st, sub in zip(lowered.joins, join_subs):
            with stage_scope("join", st.label):
                tbl = storage[st.spine]
                m = spine_masks[st.spine]
                if st.kind == "gather":
                    rid, combined = ops.shared_join_fk(
                        tbl[st.fk_col], m,
                        storage[st.pk_table]["_pk_index"],
                        scan_masks[st.pk_table])
                elif fused_rids is not None:
                    rid = fused_rids[st.key]
                    mask_r = scan_masks[st.pk_table]
                    gathered = mask_r[jnp.clip(rid, 0, mask_r.shape[0] - 1)]
                    combined = jnp.where((rid >= 0)[:, None], m & gathered,
                                         jnp.uint32(0))
                elif rid_carry is not None:
                    cap = cat.schemas[st.spine].capacity
                    dr = tbl["_dirty_rows"]
                    if st.kind == "partitioned":
                        bkeys, brows, bounds = partitions[st.pk_table]
                        rid_d = backend.join_delta(tbl[st.fk_col], dr,
                                                   bkeys, brows, bounds)
                    else:  # block: dirty-row key-equality probe (tiny PK)
                        pk_tbl = storage[st.pk_table]
                        kd = tbl[st.fk_col][jnp.clip(dr, 0, cap - 1)]
                        rid_d = locate_rows_by_key(pk_tbl[st.pk_col], kd,
                                                   pk_tbl["_valid"])
                    rid = scatter_dirty_rows(rid_carry[st.key], dr, rid_d,
                                             cap)
                    mask_r = scan_masks[st.pk_table]
                    gathered = mask_r[jnp.clip(rid, 0, mask_r.shape[0] - 1)]
                    combined = jnp.where((rid >= 0)[:, None], m & gathered,
                                         jnp.uint32(0))
                elif st.kind == "partitioned":
                    bkeys, brows, bounds = partitions[st.pk_table]
                    rid, combined = backend.join_partitioned(
                        tbl[st.fk_col], m, bkeys, brows, bounds,
                        scan_masks[st.pk_table])
                else:  # block: dense key-equality kernel, small index-less PK
                    pk_tbl = storage[st.pk_table]
                    rid, combined = backend.join_block(
                        tbl[st.fk_col], m, pk_tbl[st.pk_col],
                        scan_masks[st.pk_table], pk_tbl["_valid"])
                spine_masks[st.spine] = (combined & sub[None, :]) \
                    | (m & ~sub[None, :])
                join_rids[st.key] = rid

        # 4. shared sorts + fused per-query top-n + routing (Gamma): the
        #    sort runs over the bounded UNION of tuples wanted by the
        #    node's subscribers (Fig. 4); overflow past the cap is counted.
        results = {}
        overflow = jnp.zeros((), jnp.int32)
        for st, sub in zip(lowered.sorts, sort_subs):
            with stage_scope("sort", f"{st.spine}.{st.col}"):
                mask = spine_masks[st.spine][:, st.wlo:st.whi] & sub[None, :]
                rows_c, cmask, n_want = ops.compress_union(mask, st.union_cap)
                overflow += jnp.maximum(n_want - st.union_cap, 0)
                keys = storage[st.spine][st.col][jnp.maximum(rows_c, 0)]
                keys = jnp.where(rows_c >= 0,
                                 -keys if st.desc else keys, ops.INT_MAX)
                perm = jnp.argsort(keys, stable=True)
                rows = ops.route_topn(cmask[perm],
                                      limits[st.wlo * 32:st.whi * 32],
                                      plan.max_results, rows=rows_c[perm])
                for name, o, c in st.slots:
                    results[name] = {"rows": rows[o:o + c]}

        # 5. shared group-bys (phase 1 shared over the union, phase 2 per
        #    query)
        for st in lowered.groups:
            with stage_scope("group", f"{st.spine}.{st.agg.group_col}"):
                agg = st.agg
                tbl = storage[st.spine]
                rows_c, cmask, n_want = ops.compress_union(
                    spine_masks[st.spine][:, st.wlo:st.whi], st.union_cap)
                overflow += jnp.maximum(n_want - st.union_cap, 0)
                safe = jnp.maximum(rows_c, 0)
                gcodes = jnp.where(rows_c >= 0, tbl[agg.group_col][safe], 0)
                gvals = jnp.where(rows_c >= 0, tbl[agg.agg_col][safe], 0)
                count, ssum = backend.groupby(gcodes, gvals, cmask,
                                              agg.n_groups)
                score = ssum if agg.order_by == "sum" else count
                top_val, top_grp = jax.lax.top_k(score.T, agg.top_k)  # [q, K]
                for name, o, c in st.slots:
                    results[name] = {
                        "groups": top_grp[o:o + c].astype(jnp.int32),
                        "scores": top_val[o:o + c],
                        "counts": jnp.take_along_axis(
                            count.T[o:o + c], top_grp[o:o + c], axis=1)}

        # 6. unsorted templates route in natural row order — ONE routing
        #    pass per spine shared by all such templates
        for st, sub in zip(lowered.routes, route_subs):
            with stage_scope("route", st.spine):
                mask = spine_masks[st.spine][:, st.wlo:st.whi] & sub[None, :]
                rows_c, cmask, n_want = ops.compress_union(mask, st.union_cap)
                overflow += jnp.maximum(n_want - st.union_cap, 0)
                rows = ops.route_topn(cmask, limits[st.wlo * 32:st.whi * 32],
                                      plan.max_results, rows=rows_c)
                for name, o, c in st.slots:
                    results[name] = {"rows": rows[o:o + c]}
        results["_overflow"] = overflow

        # attach join rids so hosts can materialize joined tuples
        results["_join_rids"] = join_rids
        return results

    return post_scan
