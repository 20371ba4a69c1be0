"""Mesh-aware sharding of the always-on plan (multi-device heartbeats).

SharedDB scales shared operators by giving each one its own core (paper
§4.5); on a JAX device mesh the analogue is sharding the spine tables —
and the heartbeat carry itself — by spine-row range, so a full-rescan /
reseed beat scatters its bounded work across every shard while a
steady-state delta beat stays entirely shard-local.

Layout (the sharding contract):

  * ROW-SHARDED — every table that is NOT a join probe side.  Columns,
    validity, the carried scan words and the carried per-join rid
    arrays live as flat ``[Tp]``-leading arrays laid out in S
    contiguous shard blocks of ``Ts = Tp // S`` rows
    (``NamedSharding(mesh, P("row"))``; ``Tp`` is the table capacity
    rounded up to a multiple of S, padding rows permanently invalid).
    Each shard also keeps a PRIVATE dirty-row set of the update-batch
    rows it owns (``[S, dirty_cap]`` local row ids), so dirty rows
    route to their owning shard and the delta scan / delta join
    re-probes are per-shard gathers with no communication.
  * REPLICATED — every join PK-side table (the probe sides; dimension
    tables in TPC-W terms) is mirrored in full on every shard, plus
    the small replicated side state of sharded tables: the append
    cursor ``_n``, the dense ``_pk_index`` (global row ids) and — for
    index-less PK tables — a slim (key, valid) mirror so update
    targeting (``storage.locate_rows_by_key``) stays a replicated
    computation instead of a cross-shard reduction.

Beat structure (the whole heartbeat runs inside ONE ``shard_map``, so
every cross-shard transfer is an explicit collective in the jaxpr):

  * full / reseed beat (``build_sharded_cycle``) — replicated tables'
    predicated scan stages are computed SHARDED (each shard scans its
    row slice of the mirror) and ``all_gather``-ed back into the
    replicated words: the one collective in the system, touching every
    shard exactly once per stage.  Row-sharded stages rescan
    shard-locally.
  * delta beat (``build_sharded_delta_cycle``) — admission panes and
    dirty rows of replicated tables refresh by replicated compute from
    the mirror; row-sharded stages refresh shard-locally from their
    private dirty sets and carried words/rids.  The compiled delta
    heartbeat contains NO cross-shard collective (asserted on both the
    jaxpr and the optimized HLO by tests/test_sharding_locality.py).

Results: stages whose spine is replicated run replicated and return
final per-template results (reusing lowering's post-scan verbatim on
the filtered plan); stages on row-sharded spines return per-shard
partials — route/sort candidates with their comparison keys, group-by
partial aggregates — that ``build_merge``'s host-side merge folds into
final results at collect time.  Cross-shard result routing costs one
tiny host pass on data already bounded by the per-template limits,
instead of a device collective on every beat.

``SharedDBEngine(mesh=...)`` threads all of this through the executor;
a 1-shard mesh degrades to bit-identical behavior: padded shapes equal
the originals, each shard body sees the full row range, and the reseed
all_gather over one device is the identity.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import dataquery as dq
from repro.core import operators as ops
from repro.core.backends import (FusedJoinIn, FusedScanIn,
                                 OperatorBackend)
from repro.core.lowering import (LoweredPlan, _bind_predicates,
                                 _build_post_scan, _pane_window,
                                 _pseudo_partitions)
from repro.core.plan import CompiledPlan
from repro.core.storage import (Catalog, TableSchema, apply_updates,
                                build_key_partitions, bulk_load,
                                empty_table, locate_rows_by_key,
                                refresh_key_partitions,
                                scatter_dirty_rows)

ROW_AXIS = "row"

# replicated side-state keys of a row-sharded table (everything else in
# the table dict is a [Tp] / [S, ...] sharded leaf)
_SIDE_KEYS = ("_n", "_version", "_pk_index", "_mkey", "_mvalid")
# per-shard (stacked, NOT flat-row) leaves: leading axis is the shard
_STACKED_KEYS = ("_dirty_rows", "_dirty_n", "_dirty_overflow")


def make_mesh(shape, axis_names, devices) -> Mesh:
    """The repo's one mesh constructor: every axis is ``Auto`` — sharding
    is stated by ``shard_map`` specs and ``NamedSharding``s, never
    propagated through array types (the ``Explicit`` default of
    ``jax.make_mesh`` rejects the untyped gathers of the merge and the
    LM launcher's ``with_sharding_constraint``)."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=list(devices))


def make_row_mesh(n_shards: int) -> Mesh:
    """A 1-D ``(n_shards,)`` mesh over the first host devices."""
    devs = jax.devices()
    if len(devs) < n_shards:
        raise RuntimeError(
            f"need {n_shards} devices for a {n_shards}-shard row mesh, "
            f"have {len(devs)}; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return make_mesh((n_shards,), (ROW_AXIS,), devs[:n_shards])


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """The sharding layout derived from (plan, mesh).

    ``mirrored`` — replicated tables (every join PK side).
    ``shard_rows``/``padded`` — per-table ``Ts`` and ``Tp = S * Ts``.
    ``plan`` — the compiled plan with the PADDED catalog (capacities
    rounded up so row ranges divide evenly; at S=1 this is the original
    plan object's geometry exactly).
    """
    mesh: Mesh
    axis: str
    n_shards: int
    mirrored: Tuple[str, ...]
    shard_rows: Dict[str, int]
    padded: Dict[str, int]
    # ORIGINAL capacities: the insert commit bound.  Rows in
    # [commit_rows, padded) exist only for shard alignment and stay
    # permanently invalid — the unsharded engine would have dropped
    # any insert landing there (storage.apply_updates commit_cap).
    commit_rows: Dict[str, int]
    plan: CompiledPlan

    def is_mirrored(self, table: str) -> bool:
        return table in self.mirrored

    def schema(self, table: str) -> TableSchema:
        return self.plan.catalog.schemas[table]

    def repl_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def row_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.axis))

    def state_sharding(self, state):
        """Pytree of NamedSharding matching an engine state pytree."""
        repl, rows = self.repl_sharding(), self.row_sharding()
        out = {}
        for t, d in state.items():
            if self.is_mirrored(t):
                out[t] = {k: repl for k in d}
            else:
                out[t] = {k: (repl if k in _SIDE_KEYS else rows)
                          for k in d}
        return out


def build_shard_spec(plan: CompiledPlan, mesh: Mesh) -> ShardSpec:
    if len(mesh.axis_names) != 1:
        raise ValueError(f"row mesh must be 1-D, got {mesh.axis_names}")
    axis = mesh.axis_names[0]
    S = int(np.prod(mesh.devices.shape))
    mirrored = tuple(sorted({j.pk_table for j in plan.joins}))
    shard_rows, padded, commit_rows, schemas = {}, {}, {}, []
    for name, schema in plan.catalog.schemas.items():
        ts = -(-schema.capacity // S)
        shard_rows[name] = ts
        padded[name] = ts * S
        commit_rows[name] = schema.capacity
        schemas.append(dataclasses.replace(schema, capacity=ts * S))
    padded_plan = dataclasses.replace(plan, catalog=Catalog(schemas))
    return ShardSpec(mesh=mesh, axis=axis, n_shards=S, mirrored=mirrored,
                     shard_rows=shard_rows, padded=padded,
                     commit_rows=commit_rows, plan=padded_plan)


def check_fold_mirrors(old_plan: CompiledPlan,
                       new_plan: CompiledPlan) -> None:
    """A fold under a mesh must keep the sharded STATE layout fixed.

    Whether a table is mirrored (replicated probe side) or row-sharded
    is decided by join membership, and the two layouts store different
    leaves under different shardings — flipping a table would demand a
    cross-shard state migration mid-serve, and un-mirroring a table
    would put collectives back into the delta beats its probes ride on.
    The catalog itself is shared by construction (extend_plan refuses
    new tables), so padded capacities never move; this check closes the
    remaining degree of freedom.  Folds that only subscribe to existing
    joins, or add joins into already-mirrored PK tables, pass.

    The mirror-set comparison itself is the planlint pass
    ``analysis_static.ir_passes.lint_fold_mirrors`` (rule
    ``fold-mirror-set``); this entry point raises ``ValueError`` as
    before.
    """
    from repro.analysis_static.diagnostics import raise_on_error
    from repro.analysis_static.ir_passes import lint_fold_mirrors
    raise_on_error(lint_fold_mirrors(old_plan, new_plan),
                   exc=ValueError)


# ---------------------------------------------------------------------------
# State construction
# ---------------------------------------------------------------------------


def init_sharded_state(spec: ShardSpec, initial_data: Dict) -> Dict:
    """Padded + sharded initial state, placed on the mesh.

    Mirrored tables are full replicated table dicts (the existing
    storage layout, padded capacity).  Row-sharded tables keep their
    columns/_valid as flat ``[Tp]`` row-sharded leaves, per-shard dirty
    sets as ``[S, dirty_cap]``, and the replicated side state (append
    cursor, dense pk index, and the (key, valid) locate mirror for
    index-less PK tables).
    """
    S = spec.n_shards
    state = {}
    for name, schema in spec.plan.catalog.schemas.items():
        full = bulk_load(schema, initial_data[name]) \
            if name in initial_data else empty_table(schema)
        if spec.is_mirrored(name):
            state[name] = full
            continue
        t = {c: full[c] for c in schema.columns}
        t["_valid"] = full["_valid"]
        D = schema.dirty_cap
        Ts = spec.shard_rows[name]
        # per-shard dirty sets: LOCAL row ids, sentinel = Ts (clean)
        t["_dirty_rows"] = jnp.full((S, D), Ts, jnp.int32)
        t["_dirty_n"] = jnp.zeros((S,), jnp.int32)
        t["_dirty_overflow"] = jnp.zeros((S,), bool)
        t["_n"] = full["_n"]
        t["_version"] = full["_version"]
        if schema.indexed:
            t["_pk_index"] = full["_pk_index"]
        elif schema.pk:
            # index-less PK table: replicated (key, valid) mirror so
            # update targeting stays a replicated computation.  COPIES —
            # they live under a different sharding than the column
            # leaves they mirror, and the donated state must never hold
            # the same buffer twice.
            t["_mkey"] = jnp.array(full[schema.pk])
            t["_mvalid"] = jnp.array(full["_valid"])
        state[name] = t
    sharding = spec.state_sharding(state)
    return jax.tree.map(jax.device_put, state, sharding)


def _split_table(t: Dict) -> Tuple[Dict, Dict]:
    sh = {k: v for k, v in t.items() if k not in _SIDE_KEYS}
    side = {k: v for k, v in t.items() if k in _SIDE_KEYS}
    return sh, side


# ---------------------------------------------------------------------------
# Per-shard update apply
# ---------------------------------------------------------------------------


def _apply_shard(schema: TableSchema, spec: ShardSpec, local: Dict,
                 side: Dict, batch: Dict, offset):
    """One shard's slice of ``storage.apply_updates``.

    ``local`` holds this shard's ``[Ts]`` column slices plus its private
    dirty set; ``side`` the replicated side state.  Row targeting uses
    only replicated inputs (the dense pk index or the (key, valid)
    mirror), so every shard computes identical global rows and commits
    exactly the ones it owns — a replicated computation plus a local
    scatter, never a cross-shard reduction.  The side state is updated
    identically on every shard (deterministic, so it stays replicated).
    Semantics mirror ``apply_updates`` field for field: deletes, then
    post-delete-located column updates, then inserts, in slot order.
    """
    Ts = spec.shard_rows[schema.name]
    Tp = spec.padded[schema.name]
    t, s = dict(local), dict(side)
    touched = []                      # LOCAL dirty candidates, -1 = no-op

    if schema.pk:
        def locate(keys, mask):
            """Global row holding pk ``keys[i]`` (-1 absent/masked)."""
            if schema.indexed:
                return jnp.where(mask, s["_pk_index"][keys], -1)
            return jnp.where(
                mask, locate_rows_by_key(s["_mkey"], keys, s["_mvalid"]),
                -1)

        # deletes: invalidate owned rows; replicated side bookkeeping
        del_g = locate(batch["del_key"], batch["del_mask"])
        ok = del_g >= 0
        dl = del_g - offset
        own = ok & (dl >= 0) & (dl < Ts)
        t["_valid"] = t["_valid"].at[jnp.where(own, dl, Ts)].set(
            False, mode="drop")
        touched.append(jnp.where(own, dl, -1))
        if schema.indexed:
            s["_pk_index"] = s["_pk_index"].at[
                jnp.where(ok, batch["del_key"], schema.key_space)].set(
                -1, mode="drop")
        else:
            s["_mvalid"] = s["_mvalid"].at[jnp.where(ok, del_g, Tp)].set(
                False, mode="drop")

        # point updates, located POST-delete (arrival-order semantics)
        upd_g = locate(batch["upd_key"], batch["upd_mask"])
        ul = upd_g - offset
        uown = (upd_g >= 0) & (ul >= 0) & (ul < Ts)
        touched.append(jnp.where(uown, ul, -1))
        for ci, c in enumerate(schema.columns):
            sel = (batch["upd_col"] == ci) & uown
            rows = jnp.where(sel, ul, Ts)
            t[c] = t[c].at[rows].set(
                jnp.where(sel, batch["upd_val"], 0), mode="drop")
        if not schema.indexed:
            # the locate mirror tracks the pk COLUMN (which updates may
            # rewrite), exactly like the column itself
            pk_ci = schema.columns.index(schema.pk)
            selk = (batch["upd_col"] == pk_ci) & (upd_g >= 0)
            s["_mkey"] = s["_mkey"].at[jnp.where(selk, upd_g, Tp)].set(
                jnp.where(selk, batch["upd_val"], 0), mode="drop")

    # inserts: append at the replicated cursor; commit owned rows.  The
    # commit bound is the ORIGINAL capacity: rows in [cap_c, Tp) exist
    # only for shard alignment and must stay invalid, exactly like the
    # unsharded engine drops inserts past its capacity.
    cap_c = spec.commit_rows[schema.name]
    offs = jnp.cumsum(batch["ins_mask"].astype(jnp.int32)) - 1
    rows_g = jnp.where(batch["ins_mask"], s["_n"] + offs, Tp)
    rl = rows_g - offset
    lown = batch["ins_mask"] & (rows_g < cap_c) & (rl >= 0) & (rl < Ts)
    lrows = jnp.where(lown, rl, Ts)
    for c in schema.columns:
        t[c] = t[c].at[lrows].set(batch["ins_rows"][c], mode="drop")
    t["_valid"] = t["_valid"].at[lrows].set(True, mode="drop")
    touched.append(jnp.where(lown, rl, -1))
    s["_n"] = s["_n"] + jnp.sum(batch["ins_mask"].astype(jnp.int32))
    if schema.indexed:
        keys = jnp.where(batch["ins_mask"], batch["ins_rows"][schema.pk],
                         schema.key_space)
        # dropped inserts index as absent, matching apply_updates
        s["_pk_index"] = s["_pk_index"].at[keys].set(
            jnp.where(batch["ins_mask"] & (rows_g < cap_c), rows_g,
                      -1).astype(jnp.int32), mode="drop")
    elif schema.pk:
        irows = jnp.where(batch["ins_mask"] & (rows_g < cap_c), rows_g,
                          Tp)
        s["_mkey"] = s["_mkey"].at[irows].set(
            batch["ins_rows"][schema.pk], mode="drop")
        s["_mvalid"] = s["_mvalid"].at[irows].set(True, mode="drop")
    s["_version"] = s["_version"] + 1

    # private dirty set: the LOCAL rows this shard's slice was touched at
    cand = jnp.concatenate([x.astype(jnp.int32) for x in touched])
    D = t["_dirty_rows"].shape[0]
    if cand.shape[0] == 0:
        t["_dirty_rows"] = jnp.full((D,), Ts, jnp.int32)
        t["_dirty_n"] = jnp.zeros((), jnp.int32)
        t["_dirty_overflow"] = jnp.zeros((), bool)
        return t, s
    mark = jnp.zeros((Ts,), bool).at[
        jnp.where(cand >= 0, cand, Ts)].set(True, mode="drop")
    count = jnp.sum(mark.astype(jnp.int32))
    t["_dirty_rows"] = jnp.nonzero(
        mark, size=D, fill_value=Ts)[0].astype(jnp.int32)
    t["_dirty_n"] = jnp.minimum(count, D)
    t["_dirty_overflow"] = count > D
    return t, s


# ---------------------------------------------------------------------------
# Scan-stage helpers (shared by the replicated and shard-local paths)
# ---------------------------------------------------------------------------


def _stage_full(st, backend, covered, pidx, tbl, queries):
    cols = jnp.stack([tbl[c] for c in st.cols])
    _, lo, hi = _bind_predicates(st, covered, pidx, queries)
    return backend.scan(cols, lo, hi, tbl["_valid"])


def _stage_degenerate(st, covered, valid, queries):
    base = st.wlo * 32
    act = queries["active"][base:base + st.q_window]
    return dq.pack(valid[:, None] & (act & covered)[None])


def _stage_delta(st, backend, covered, pidx, tbl, carry_words, queries,
                 dirty_rows, dirty_overflow, capacity):
    """Admission pane + dirty rows against carried words (one stage).

    Identical math to ``lowering.build_delta_cycle``'s scan block; the
    caller picks the row universe: the full mirror (``capacity = Tp``,
    replicated) or one shard's slice (``capacity = Ts``, local dirty
    set).  Returns (merged words, overflow count).
    """
    base = st.wlo * 32
    _, lo, hi = _bind_predicates(st, covered, pidx, queries)
    cols = jnp.stack([tbl[c] for c in st.cols])
    w = st.whi - st.wlo
    A = st.delta_words
    qd = queries["changed"][base:base + st.q_window] & covered
    wch = jnp.any(qd.reshape(w, 32), axis=1)
    first = jnp.argmax(wch).astype(jnp.int32)
    last = (w - 1 - jnp.argmax(wch[::-1])).astype(jnp.int32)
    span = jnp.where(jnp.any(wch), last - first + 1, 0)
    over = jnp.maximum(span - A, 0)
    w0 = jnp.minimum(first, w - A)
    lo_a = jax.lax.dynamic_slice(lo, (0, w0 * 32), (lo.shape[0], A * 32))
    hi_a = jax.lax.dynamic_slice(hi, (0, w0 * 32), (hi.shape[0], A * 32))
    pane = backend.scan(cols, lo_a, hi_a, tbl["_valid"])
    m = jax.lax.dynamic_update_slice(carry_words, pane, (0, w0))
    dwords = backend.scan_delta(cols, lo, hi, tbl["_valid"], dirty_rows)
    m = scatter_dirty_rows(m, dirty_rows, dwords, capacity)
    over = over + dirty_overflow.astype(jnp.int32)
    return m, over


def _fused_scan_in(st, covered, pidx, tbl, carry_words, queries,
                   dirty_rows, dirty_overflow, dn):
    """One stage's FusedScanIn + overflow count: the ``_stage_delta``
    prologue (predicate bind, pane geometry, pane slices) with the
    compute deferred to the single fused op."""
    _, lo, hi = _bind_predicates(st, covered, pidx, queries)
    cols = jnp.stack([tbl[c] for c in st.cols])
    A = st.delta_words
    span, w0, over = _pane_window(st, covered, queries["changed"])
    lo_a = jax.lax.dynamic_slice(lo, (0, w0 * 32), (lo.shape[0], A * 32))
    hi_a = jax.lax.dynamic_slice(hi, (0, w0 * 32), (hi.shape[0], A * 32))
    return FusedScanIn(
        cols=cols, lo=lo, hi=hi, lo_p=lo_a, hi_p=hi_a,
        valid=tbl["_valid"], carry=carry_words, w0=w0, span=span,
        rows=dirty_rows, dn=dn.astype(jnp.int32)), \
        over + dirty_overflow.astype(jnp.int32)


def _pad_words(st, m, W):
    return jnp.pad(m, ((0, 0), (st.wlo, W - st.whi)))


# ---------------------------------------------------------------------------
# The sharded heartbeat
# ---------------------------------------------------------------------------


def _build_impl(lowered: LoweredPlan, backend: OperatorBackend,
                spec: ShardSpec, delta: bool, delta_joins: bool):
    plan = spec.plan                       # padded catalog
    cat = plan.catalog
    W = lowered.W
    S = spec.n_shards
    mirrored = set(spec.mirrored)
    sharded_tables = [t for t in cat.schemas if t not in mirrored]

    # stage classification: replicated (mirror) vs shard-local
    mi_scans = [st for st in lowered.scans if st.table in mirrored]
    sh_scans = [st for st in lowered.scans if st.table not in mirrored]
    sh_joins = [j for j in lowered.joins if j.spine not in mirrored]
    mi_joins = tuple(j for j in lowered.joins if j.spine in mirrored)
    sh_sorts = [s for s in lowered.sorts if s.spine not in mirrored]
    mi_sorts = tuple(s for s in lowered.sorts if s.spine in mirrored)
    sh_groups = [g for g in lowered.groups if g.spine not in mirrored]
    mi_groups = tuple(g for g in lowered.groups if g.spine in mirrored)
    sh_routes = [r for r in lowered.routes if r.spine not in mirrored]
    mi_routes = tuple(r for r in lowered.routes if r.spine in mirrored)

    # mirrored-spine post stages reuse lowering's post-scan verbatim on
    # the filtered (padded-catalog) plan: replicated compute
    mirror_post = _build_post_scan(
        dataclasses.replace(lowered, plan=plan, joins=mi_joins,
                            sorts=mi_sorts, groups=mi_groups,
                            routes=mi_routes), backend)

    # partitioned-join layouts over the PADDED mirror (same bucket_cap,
    # bucket count rounded up so padding rows fit; identical at S=1)
    part_specs = {}
    for j in lowered.joins:
        if j.kind == "partitioned":
            n_parts = -(-spec.padded[j.pk_table] // j.bucket_cap)
            part_specs.setdefault(j.pk_table,
                                  (j.pk_col, n_parts, j.bucket_cap))

    scan_covered = {st.table: jnp.asarray(st.covered)
                    for st in lowered.scans}
    scan_pidx = {st.table: jnp.asarray(st.param_idx)
                 for st in lowered.scans}
    join_subs = {j.key: jnp.asarray(j.sub_mask) for j in lowered.joins}
    sort_subs = [jnp.asarray(s.sub_mask) for s in sh_sorts]
    route_subs = [jnp.asarray(r.sub_mask) for r in sh_routes]
    limits = jnp.asarray(lowered.limits)
    carried_sh_spines = sorted({j.spine for j in sh_joins
                                if j.kind != "gather"})
    # fused delta beat: every pane, dirty rescan and dirty probe — over
    # mirrors AND shard-local slices — collapses into ONE backend op per
    # shard (a backend without fused_delta keeps the chained stages)
    fused = delta and backend.fused_delta is not None

    def body(sh_in: Dict, repl_in: Dict):
        """One shard's slice of the heartbeat (the whole beat runs in
        here under shard_map, so every cross-shard transfer is an
        explicit collective — and the delta flavour has none)."""
        idx = jax.lax.axis_index(spec.axis)
        queries = repl_in["queries"]
        updates = repl_in["updates"]

        # -- 1. update apply: mirrors replicated, sharded tables local
        # (insert commits bounded by the ORIGINAL capacity either way —
        # alignment padding rows stay permanently invalid)
        mirror = {t: apply_updates(cat.schemas[t], repl_in["mirror"][t],
                                   updates[t],
                                   commit_cap=spec.commit_rows[t])
                  for t in spec.mirrored}
        tables, sides = {}, {}
        for t in sharded_tables:
            local = {k: (v[0] if k in _STACKED_KEYS else v)
                     for k, v in sh_in["tables"][t].items()}
            tables[t], sides[t] = _apply_shard(
                cat.schemas[t], spec, local, repl_in["sides"][t],
                updates[t], idx * spec.shard_rows[t])

        # -- 2. key partitions (replicated: derived from the mirror)
        partitions, rebuilt = {}, {}
        for t, (pk_col, n_parts, bucket_cap) in part_specs.items():
            m = mirror[t]
            if delta:
                partitions[t], rebuilt[t] = refresh_key_partitions(
                    m, pk_col, n_parts, bucket_cap,
                    repl_in["carry_parts"][t])
            else:
                partitions[t] = build_key_partitions(
                    m[pk_col], m["_valid"], n_parts, bucket_cap)
                rebuilt[t] = jnp.ones((), bool)

        # -- 3. mirrored scan stages
        mirror_words = {}                 # window-local, replicated
        delta_over_repl = jnp.zeros((), jnp.int32)   # identical per shard
        delta_over_local = jnp.zeros((), jnp.int32)  # this shard's own
        fused_scan, fused_own = [], []    # inputs + ("mi"/"sh", stage)
        for st in mi_scans:
            mt = mirror[st.table]
            if not st.cols:
                mirror_words[st.table] = _stage_degenerate(
                    st, scan_covered[st.table], mt["_valid"], queries)
            elif fused:
                e, o = _fused_scan_in(
                    st, scan_covered[st.table], scan_pidx[st.table], mt,
                    repl_in["carry_m"][st.table], queries,
                    mt["_dirty_rows"], mt["_dirty_overflow"],
                    mt["_dirty_n"])
                fused_scan.append(e)
                fused_own.append(("mi", st))
                delta_over_repl = delta_over_repl + o
            elif delta:
                # replicated maintenance: pane + global dirty rows
                m, o = _stage_delta(
                    st, backend, scan_covered[st.table],
                    scan_pidx[st.table], mt,
                    repl_in["carry_m"][st.table], queries,
                    mt["_dirty_rows"], mt["_dirty_overflow"],
                    spec.padded[st.table])
                mirror_words[st.table] = m
                delta_over_repl = delta_over_repl + o
            else:
                # reseed: each shard scans its row SLICE of the mirror,
                # then one all_gather rebuilds the replicated words —
                # the full rescan is spread over every shard exactly
                # once (the only collective in the system)
                Ts = spec.shard_rows[st.table]
                sl = {c: jax.lax.dynamic_slice_in_dim(mt[c], idx * Ts,
                                                      Ts)
                      for c in st.cols}
                sl["_valid"] = jax.lax.dynamic_slice_in_dim(
                    mt["_valid"], idx * Ts, Ts)
                pane = _stage_full(st, backend, scan_covered[st.table],
                                   scan_pidx[st.table], sl, queries)
                mirror_words[st.table] = jax.lax.all_gather(
                    pane, spec.axis, tiled=True)

        # -- 4. row-sharded scan stages (shard-local, both flavours)
        sh_words = {}
        scan_masks = {}
        for st in sh_scans:
            tbl = tables[st.table]
            if not st.cols:
                m = _stage_degenerate(st, scan_covered[st.table],
                                      tbl["_valid"], queries)
            elif fused:
                e, o = _fused_scan_in(
                    st, scan_covered[st.table], scan_pidx[st.table],
                    tbl, sh_in["carry"][st.table], queries,
                    tbl["_dirty_rows"], tbl["_dirty_overflow"],
                    tbl["_dirty_n"])
                fused_scan.append(e)
                fused_own.append(("sh", st))
                delta_over_local = delta_over_local + o
                continue
            elif delta:
                m, o = _stage_delta(
                    st, backend, scan_covered[st.table],
                    scan_pidx[st.table], tbl, sh_in["carry"][st.table],
                    queries, tbl["_dirty_rows"], tbl["_dirty_overflow"],
                    spec.shard_rows[st.table])
                delta_over_local = delta_over_local + o
                sh_words[st.table] = m
            else:
                m = _stage_full(st, backend, scan_covered[st.table],
                                scan_pidx[st.table], tbl, queries)
                sh_words[st.table] = m
            scan_masks[st.table] = _pad_words(st, m, W)

        # -- 4b. the ONE fused delta op: every deferred pane/dirty/probe
        #        unit — mirror and shard-local alike — in a single
        #        backend launch; the probe sides are replicated so the
        #        whole call is shard-local math (no collective)
        delta_probe = delta and delta_joins
        fused_join, fused_jkeys = [], []
        if fused and delta_probe:
            for st in sh_joins:
                if st.kind == "gather":
                    continue
                tbl = tables[st.spine]
                if st.kind == "partitioned":
                    bkeys, brows, bounds = partitions[st.pk_table]
                else:  # block: single-bucket pseudo-partitions
                    bkeys, brows, bounds = _pseudo_partitions(
                        mirror[st.pk_table], st.pk_col)
                fused_join.append(FusedJoinIn(
                    keys=tbl[st.fk_col], rows=tbl["_dirty_rows"],
                    dn=tbl["_dirty_n"].astype(jnp.int32),
                    bkeys=bkeys, brows=brows, bounds=bounds,
                    rid_carry=sh_in["rids"][st.key]))
                fused_jkeys.append(st.key)
            for st in mi_joins:
                if st.kind == "gather":
                    continue
                mt = mirror[st.spine]
                if st.kind == "partitioned":
                    bkeys, brows, bounds = partitions[st.pk_table]
                else:
                    bkeys, brows, bounds = _pseudo_partitions(
                        mirror[st.pk_table], st.pk_col)
                fused_join.append(FusedJoinIn(
                    keys=mt[st.fk_col], rows=mt["_dirty_rows"],
                    dn=mt["_dirty_n"].astype(jnp.int32),
                    bkeys=bkeys, brows=brows, bounds=bounds,
                    rid_carry=repl_in["rids_m"][st.key]))
                fused_jkeys.append(st.key)
        fused_rids = None
        if fused and (fused_scan or fused_join):
            words, rids = backend.fused_delta(tuple(fused_scan),
                                              tuple(fused_join))
            for (side, st), m in zip(fused_own, words):
                if side == "mi":
                    mirror_words[st.table] = m
                else:
                    sh_words[st.table] = m
                    scan_masks[st.table] = _pad_words(st, m, W)
            if delta_probe:
                fused_rids = dict(zip(fused_jkeys, rids))
        mirror_masks = {st.table: _pad_words(st, mirror_words[st.table],
                                             W) for st in mi_scans}

        # -- 5. joins on row-sharded spines (probe sides replicated:
        #       partitions / pk index / mirror words — shard-local math)
        spine_masks = dict(scan_masks)
        sh_rids = {}
        for st in sh_joins:
            tbl = tables[st.spine]
            m = spine_masks[st.spine]
            mask_r = mirror_masks[st.pk_table]
            Ts = spec.shard_rows[st.spine]
            if st.kind == "gather":
                rid, combined = ops.shared_join_fk(
                    tbl[st.fk_col], m, mirror[st.pk_table]["_pk_index"],
                    mask_r)
            elif delta_probe:
                if fused_rids is not None:
                    rid = fused_rids[st.key]   # merged in the fused op
                else:
                    dr = tbl["_dirty_rows"]
                    if st.kind == "partitioned":
                        bkeys, brows, bounds = partitions[st.pk_table]
                        rid_d = backend.join_delta(tbl[st.fk_col], dr,
                                                   bkeys, brows, bounds)
                    else:
                        pk_tbl = mirror[st.pk_table]
                        kd = tbl[st.fk_col][jnp.clip(dr, 0, Ts - 1)]
                        rid_d = locate_rows_by_key(pk_tbl[st.pk_col],
                                                   kd,
                                                   pk_tbl["_valid"])
                    rid = scatter_dirty_rows(sh_in["rids"][st.key], dr,
                                             rid_d, Ts)
                gathered = mask_r[jnp.clip(rid, 0, mask_r.shape[0] - 1)]
                combined = jnp.where((rid >= 0)[:, None], m & gathered,
                                     jnp.uint32(0))
            elif st.kind == "partitioned":
                bkeys, brows, bounds = partitions[st.pk_table]
                rid, combined = backend.join_partitioned(
                    tbl[st.fk_col], m, bkeys, brows, bounds, mask_r)
            else:
                pk_tbl = mirror[st.pk_table]
                rid, combined = backend.join_block(
                    tbl[st.fk_col], m, pk_tbl[st.pk_col], mask_r,
                    pk_tbl["_valid"])
            sub = join_subs[st.key]
            spine_masks[st.spine] = (combined & sub[None, :]) \
                | (m & ~sub[None, :])
            sh_rids[st.key] = rid
        if delta_probe:
            for spine in carried_sh_spines:
                delta_over_local = delta_over_local + \
                    tables[spine]["_dirty_overflow"].astype(jnp.int32)

        # -- 6. per-shard partials for row-sharded sort/group/route
        #       stages (merged host-side at collect; shard-local here)
        partials = {}
        over_local = jnp.zeros((), jnp.int32)
        for st, sub in zip(sh_sorts, sort_subs):
            mask = spine_masks[st.spine][:, st.wlo:st.whi] & sub[None, :]
            rows_c, cmask, n_want = ops.compress_union(mask,
                                                       st.union_cap)
            over_local = over_local + jnp.maximum(
                n_want - st.union_cap, 0)
            tbl = tables[st.spine]
            keys = tbl[st.col][jnp.maximum(rows_c, 0)]
            keys = jnp.where(rows_c >= 0,
                             -keys if st.desc else keys, ops.INT_MAX)
            perm = jnp.argsort(keys, stable=True)
            rows = ops.route_topn(cmask[perm],
                                  limits[st.wlo * 32:st.whi * 32],
                                  plan.max_results, rows=rows_c[perm])
            ksel = tbl[st.col][jnp.clip(rows, 0,
                                        spec.shard_rows[st.spine] - 1)]
            kcmp = jnp.where(rows >= 0, -ksel if st.desc else ksel,
                             ops.INT_MAX)
            offset = idx * spec.shard_rows[st.spine]
            rows_g = jnp.where(rows >= 0, rows + offset, -1)
            for name, o, c in st.slots:
                partials[name] = {"rows": rows_g[o:o + c][None],
                                  "keys": kcmp[o:o + c][None]}
        for st in sh_groups:
            agg = st.agg
            tbl = tables[st.spine]
            rows_c, cmask, n_want = ops.compress_union(
                spine_masks[st.spine][:, st.wlo:st.whi], st.union_cap)
            over_local = over_local + jnp.maximum(
                n_want - st.union_cap, 0)
            safe = jnp.maximum(rows_c, 0)
            gcodes = jnp.where(rows_c >= 0, tbl[agg.group_col][safe], 0)
            gvals = jnp.where(rows_c >= 0, tbl[agg.agg_col][safe], 0)
            count, ssum = backend.groupby(gcodes, gvals, cmask,
                                          agg.n_groups)
            gkey = f"group:{st.spine}:{agg.group_col}:{agg.agg_col}"
            partials[gkey] = {"count": count[None], "sum": ssum[None]}
        for st, sub in zip(sh_routes, route_subs):
            mask = spine_masks[st.spine][:, st.wlo:st.whi] & sub[None, :]
            rows_c, cmask, n_want = ops.compress_union(mask,
                                                       st.union_cap)
            over_local = over_local + jnp.maximum(
                n_want - st.union_cap, 0)
            rows = ops.route_topn(cmask,
                                  limits[st.wlo * 32:st.whi * 32],
                                  plan.max_results, rows=rows_c)
            offset = idx * spec.shard_rows[st.spine]
            rows_g = jnp.where(rows >= 0, rows + offset, -1)
            for name, o, c in st.slots:
                partials[name] = {"rows": rows_g[o:o + c][None]}

        # -- 7. mirrored-spine post stages: replicated, final results
        mi_rid_carry = None
        if delta_probe:
            mi_rid_carry = {j.key: repl_in["rids_m"][j.key]
                            for j in mi_joins if j.kind != "gather"}
            for spine in sorted({j.spine for j in mi_joins
                                 if j.kind != "gather"}):
                delta_over_repl = delta_over_repl + \
                    mirror[spine]["_dirty_overflow"].astype(jnp.int32)
        mi_storage = dict(mirror)
        mi_fused = None
        if fused_rids is not None:
            mi_fused = {j.key: fused_rids[j.key] for j in mi_joins
                        if j.kind != "gather"}
        mi_results = mirror_post(mi_storage, partitions, mirror_masks,
                                 rid_carry=mi_rid_carry,
                                 fused_rids=mi_fused)

        # -- 8. bundle outputs: (row-sharded, replicated)
        sh_out = {
            "tables": {t: {k: (v[None] if k in _STACKED_KEYS else v)
                           for k, v in tables[t].items()}
                       for t in sharded_tables},
            "words": sh_words,
            "rids": sh_rids,
            "partials": partials,
            "overflow": over_local[None],
        }
        if delta:
            sh_out["delta_overflow"] = delta_over_local[None]
        repl_out = {
            "mirror": mirror,
            "sides": sides,
            "mirror_words": mirror_words,
            "parts": partitions,
            "rebuilt": rebuilt,
            "results": mi_results,
        }
        if delta:
            repl_out["delta_overflow"] = delta_over_repl
        return sh_out, repl_out

    smap = jax.shard_map(body, mesh=spec.mesh,
                         in_specs=(P(spec.axis), P()),
                         out_specs=(P(spec.axis), P()), check_vma=False)

    def cycle(state, carry, rid_carry, queries, updates):
        sh_tables, sides = {}, {}
        for t in sharded_tables:
            sh_tables[t], sides[t] = _split_table(state[t])
        sh_in = {"tables": sh_tables}
        repl_in = {
            "mirror": {t: state[t] for t in spec.mirrored},
            "sides": sides,
            "queries": queries,
            "updates": updates,
        }
        if delta:
            sh_in["carry"] = {st.table: carry["scan"][st.table]
                              for st in sh_scans if st.cols}
            repl_in["carry_m"] = {st.table: carry["scan"][st.table]
                                  for st in mi_scans if st.cols}
            repl_in["carry_parts"] = carry["parts"]
        if delta and delta_joins:
            sh_in["rids"] = {j.key: rid_carry[j.key] for j in sh_joins
                             if j.kind != "gather"}
            repl_in["rids_m"] = {j.key: rid_carry[j.key]
                                 for j in mi_joins
                                 if j.kind != "gather"}
        sh_out, repl_out = smap(sh_in, repl_in)

        state_out = {}
        for t in spec.mirrored:
            state_out[t] = repl_out["mirror"][t]
        for t in sharded_tables:
            state_out[t] = {**sh_out["tables"][t],
                            **repl_out["sides"][t]}
        new_carry = {"scan": {**sh_out["words"],
                              **{st.table:
                                 repl_out["mirror_words"][st.table]
                                 for st in mi_scans if st.cols}},
                     "parts": repl_out["parts"]}
        results = dict(repl_out["results"])
        results["_join_rids"] = {**results["_join_rids"],
                                 **sh_out["rids"]}
        results["_overflow_sh"] = sh_out["overflow"]
        results["_shard"] = sh_out["partials"]
        results["_parts_rebuilt"] = repl_out["rebuilt"]
        if delta:
            results["_delta_overflow_sh"] = sh_out["delta_overflow"]
            results["_delta_overflow"] = repl_out["delta_overflow"]
        return state_out, new_carry, results

    if not delta:
        return lambda state, queries, updates: cycle(
            state, None, None, queries, updates)
    if delta_joins:
        return cycle
    return lambda state, carry, queries, updates: cycle(
        state, carry, None, queries, updates)


def build_sharded_cycle(lowered: LoweredPlan, backend: OperatorBackend,
                        spec: ShardSpec):
    """Full-rescan / reseed heartbeat over the mesh.

    Same signature and carry/results contract as ``lowering.build_cycle``
    (the sharded executor is a drop-in): the reseed work is scattered —
    every shard rescans its own row range exactly once, mirrored stages
    re-assemble via one all_gather per stage.
    """
    return _build_impl(lowered, backend, spec, delta=False,
                       delta_joins=False)


def build_sharded_delta_cycle(lowered: LoweredPlan,
                              backend: OperatorBackend, spec: ShardSpec,
                              delta_joins: bool = False):
    """Incremental heartbeat over the mesh — entirely shard-local.

    Same signature as ``lowering.build_delta_cycle``.  Dirty rows route
    to their owning shard (the per-shard dirty sets filled at update
    apply), admission panes refresh per shard (or replicated, for the
    mirrors), and carried rids merge shard-locally; the compiled beat
    contains no cross-shard collective.
    """
    return _build_impl(lowered, backend, spec, delta=True,
                       delta_joins=delta_joins)


# ---------------------------------------------------------------------------
# Host-side result merge (cross-shard routing at collect time)
# ---------------------------------------------------------------------------


def build_merge(lowered: LoweredPlan, spec: ShardSpec):
    """Cross-shard result routing, split into an ON-DEVICE merge and a
    host assemble: ``(device_merge, assemble)``.

    ``device_merge(shard_partials)`` is a jitted pytree function over
    ``results["_shard"]``: row-sharded route/sort templates merge their
    per-shard candidate lists with one stable device argsort per
    template — shard order IS global row order, so a stable sort on the
    returned comparison keys reproduces the unsharded sort exactly (key
    ties break by shard then local row, the global row order) — and
    group templates sum the per-shard partial aggregates before a
    device top-k.  The executor launches it right after the cycle at
    DISPATCH time, so the merge overlaps the pipeline and ``collect()``
    does no host-side key-merge at all.

    ``assemble(results, merged)`` is the host epilogue: per-template
    passthrough of mirrored (already final) results, the merged device
    arrays, and scalar overflow sums.  At S=1 every merge is an
    identity.
    """
    mirrored = set(spec.mirrored)
    R = spec.plan.max_results
    limits = lowered.limits
    sort_tpl, route_tpl, group_tpl = {}, {}, {}
    for st in lowered.sorts:
        if st.spine not in mirrored:
            for name, o, c in st.slots:
                sort_tpl[name] = (st, o, c)
    for st in lowered.routes:
        if st.spine not in mirrored:
            for name, o, c in st.slots:
                route_tpl[name] = (st, o, c)
    for st in lowered.groups:
        if st.spine not in mirrored:
            gkey = f"group:{st.spine}:{st.agg.group_col}:" \
                   f"{st.agg.agg_col}"
            for name, o, c in st.slots:
                group_tpl[name] = (st, gkey, o, c)

    def _merge_ordered(rows, keys, lim):
        """rows/keys [S, c, R] per-shard candidates (prefix-filled, -1
        padded, each in key order), lim int32[c] -> [c, R] first ``lim``
        rows per slot in global key order, -1 padded.  Stable: equal
        keys resolve in shard order == global row order."""
        c = rows.shape[1]
        flat_r = jnp.transpose(rows, (1, 0, 2)).reshape(c, -1)
        flat_k = jnp.transpose(keys, (1, 0, 2)).reshape(c, -1)
        order = jnp.argsort(flat_k, axis=1, stable=True)
        cand = jnp.take_along_axis(flat_r, order, axis=1)
        valid = cand >= 0
        pos = jnp.cumsum(valid, axis=1) - 1       # rank among survivors
        keep = valid & (pos < lim[:, None])
        out = jnp.full((c, R), -1, jnp.int32)
        return out.at[jnp.arange(c)[:, None],
                      jnp.where(keep, pos, R)].set(
            jnp.where(keep, cand, -1), mode="drop")

    def device_merge(shard) -> Dict:
        merged = {}
        for name, (st, o, c) in sort_tpl.items():
            base = st.wlo * 32
            lim = jnp.asarray(np.minimum(
                limits[base + o:base + o + c], R).astype(np.int32))
            p = shard[name]
            merged[name] = {"rows": _merge_ordered(p["rows"], p["keys"],
                                                   lim)}
        for name, (st, o, c) in route_tpl.items():
            base = st.wlo * 32
            lim = jnp.asarray(np.minimum(
                limits[base + o:base + o + c], R).astype(np.int32))
            rows = shard[name]["rows"]
            # natural order == global row order: merge on the row id
            keys = jnp.where(rows >= 0, rows, ops.INT_MAX)
            merged[name] = {"rows": _merge_ordered(rows, keys, lim)}
        done = set()
        for name, (st, gkey, o, c) in group_tpl.items():
            agg = st.agg
            if gkey not in done:
                done.add(gkey)
                merged[gkey] = {
                    "count": jnp.sum(shard[gkey]["count"], axis=0),
                    "sum": jnp.sum(shard[gkey]["sum"], axis=0)}
        for name, (st, gkey, o, c) in group_tpl.items():
            agg = st.agg
            count = merged[gkey]["count"]
            score = merged[gkey]["sum"] if agg.order_by == "sum" \
                else count
            cols_mat = score[:, o:o + c].T                  # [c, G]
            order = jnp.argsort(-cols_mat, axis=1,
                                stable=True)[:, :agg.top_k]
            merged[name] = {
                "groups": order.astype(jnp.int32),
                "scores": jnp.take_along_axis(cols_mat, order, axis=1),
                "counts": jnp.take_along_axis(count[:, o:o + c].T,
                                              order, axis=1)}
        return merged

    def assemble(results, merged) -> Dict:
        out = {}
        for name in spec.plan.templates:
            if name in sort_tpl or name in route_tpl or \
                    name in group_tpl:
                out[name] = merged[name]               # device-merged
            else:
                out[name] = results[name]              # mirrored: final
        out["_overflow"] = (
            int(results["_overflow"])
            + int(np.asarray(results["_overflow_sh"]).sum()))
        if "_delta_overflow" in results:
            out["_delta_overflow"] = (
                int(results["_delta_overflow"])
                + int(np.asarray(results["_delta_overflow_sh"]).sum()))
        out["_parts_rebuilt"] = results["_parts_rebuilt"]
        out["_join_rids"] = results["_join_rids"]
        return out

    return jax.jit(device_merge), assemble
