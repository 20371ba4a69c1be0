"""Query-at-a-time baseline engine (the paper's MySQL / "SystemX" role).

One query compiles to one small plan (per template, like a prepared
statement): predicate-pushdown scan -> bounded candidate extraction
(modeling index-assisted access) -> per-query join gathers -> per-query
sort -> limit.  Work grows LINEARLY with the number of queries — the
behaviour SharedDB's shared plan is designed to beat (paper Figs. 10/11).

Results are bit-identical to the shared engine (property-tested).
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import CompiledPlan, QueryTemplate
from repro.core.executor import Ticket
from repro.core.storage import locate_rows_by_key

INT_MIN = -2147483647
INT_MAX = 2147483647


class QueryAtATimeEngine:
    def __init__(self, plan: CompiledPlan,
                 initial_data: Dict[str, Dict[str, np.ndarray]],
                 candidate_cap=4096, jit: bool = True):
        """candidate_cap: int, or {template: int}; a template whose spine
        has no pushdown-able predicate (e.g. best_sellers) needs the full
        spine capacity for exact results — a real system would use an
        index; the cap models that access path's selectivity."""
        self.plan = plan
        self.caps = candidate_cap
        self.state = plan.catalog.init_state(initial_data)
        self._fns = {}
        # preallocated per-template parameter staging (mirrors the shared
        # engine's packed admission: fill in place, one transfer per
        # dispatch).  The transfer below uses jnp.array (copy=True): a
        # plain asarray can be ZERO-copy on the CPU backend, and an
        # in-flight dispatch must not see a later dispatch's overwrite.
        self._param_bufs = {}
        for name, tpl in plan.templates.items():
            fn = self._build(tpl)
            self._fns[name] = jax.jit(fn) if jit else fn
            self._param_bufs[name] = np.zeros((max(len(tpl.preds), 1), 2),
                                              np.int32)

    def _cap_for(self, tpl: QueryTemplate) -> int:
        spine_cap = self.plan.catalog.schemas[tpl.spine].capacity
        if isinstance(self.caps, dict):
            k = self.caps.get(tpl.name, 4096)
        else:
            k = self.caps
        has_spine_pred = any(p.table == tpl.spine for p in tpl.preds)
        if not has_spine_pred:
            return spine_cap  # exactness requires the full spine
        return min(k, spine_cap)

    # ------------------------------------------------------------------
    def _build(self, tpl: QueryTemplate):
        plan = self.plan
        K = self._cap_for(tpl)
        schema = plan.catalog.schemas[tpl.spine]

        def fn(storage, params):
            """params: int32[n_preds, 2].  One query at a time."""
            spine = storage[tpl.spine]
            ok = spine["_valid"]
            # push down spine predicates
            for pi, p in enumerate(tpl.preds):
                if p.table != tpl.spine:
                    continue
                col = spine[p.col]
                ok &= (col >= params[pi, 0]) & (col <= params[pi, 1])
            # bounded candidate extraction (index-assisted access model)
            cand = jnp.nonzero(ok, size=K, fill_value=schema.capacity)[0]
            live = cand < schema.capacity
            cand_safe = jnp.minimum(cand, schema.capacity - 1)

            # per-query joins + joined-table predicates
            for j in tpl.joins:
                fk = spine[j.fk_col][cand_safe]
                pk_tbl = storage[j.pk_table]
                pk_schema = plan.catalog.schemas[j.pk_table]
                if pk_schema.indexed:
                    idx = pk_tbl["_pk_index"]
                    safe_fk = jnp.clip(fk, 0, idx.shape[0] - 1)
                    rid = jnp.where((fk >= 0) & (fk < idx.shape[0]),
                                    idx[safe_fk], -1)
                else:
                    # no dense index: key-equality lookup (mirrors the
                    # shared engine's block-join access path)
                    rid = locate_rows_by_key(pk_tbl[pk_schema.pk], fk,
                                             pk_tbl["_valid"])
                live &= rid >= 0
                rid_safe = jnp.clip(rid, 0, pk_tbl["_valid"].shape[0] - 1)
                live &= pk_tbl["_valid"][rid_safe]
                for pi, p in enumerate(tpl.preds):
                    if p.table != j.pk_table:
                        continue
                    col = pk_tbl[p.col][rid_safe]
                    live &= (col >= params[pi, 0]) & (col <= params[pi, 1])

            if tpl.group is not None:
                g = tpl.group
                codes = spine[g.group_col][cand_safe]
                vals = spine[g.agg_col][cand_safe]
                w = live.astype(jnp.float32)
                count = jax.ops.segment_sum(w, codes,
                                            num_segments=g.n_groups)
                ssum = jax.ops.segment_sum(w * vals, codes,
                                           num_segments=g.n_groups)
                score = ssum if g.order_by == "sum" else count
                top_val, top_grp = jax.lax.top_k(score, g.top_k)
                return {"groups": top_grp.astype(jnp.int32),
                        "scores": top_val,
                        "counts": count[top_grp]}

            order = jnp.arange(K)
            if tpl.sort_col:
                key = spine[tpl.sort_col][cand_safe]
                key = jnp.where(live, -key if tpl.sort_desc else key,
                                INT_MAX)
                order = jnp.argsort(key, stable=True)
            else:
                order = jnp.argsort(jnp.where(live, cand, INT_MAX),
                                    stable=True)
            rows = jnp.where(live[order], cand[order], -1)
            n = min(plan.max_results, K)
            out = jnp.full((plan.max_results,), -1, jnp.int32)
            lim = min(tpl.limit, plan.max_results)
            keep = jnp.arange(n) < lim
            return {"rows": out.at[:n].set(
                jnp.where(keep, rows[:n], -1)).astype(jnp.int32)}

        return fn

    # ------------------------------------------------------------------
    def dispatch(self, template: str, params: Dict) -> Ticket:
        """Launch one query's prepared plan; returns while the device
        still computes (the same dispatch/collect protocol as
        SharedDBEngine, so engine comparisons measure like with like)."""
        tpl = self.plan.templates[template]
        arr = self._param_bufs[template]
        for pi in range(len(tpl.preds)):
            arr[pi] = params[pi]
        t = Ticket(0, template, params, time.perf_counter())
        t.result = self._fns[template](self.state, jnp.array(arr))
        return t

    def collect(self, t: Ticket) -> Ticket:
        """Block on a dispatched query and materialize its result."""
        t.result = jax.tree.map(np.asarray, t.result)
        t.done_time = time.perf_counter()
        return t

    def execute(self, template: str, params: Dict) -> Ticket:
        return self.collect(self.dispatch(template, params))

    def execute_batch(self, items: List) -> List[Ticket]:
        """Queries one at a time — the traditional model."""
        return [self.execute(name, params) for name, params in items]

    def apply_update(self, table: str, kind: str, payload: Dict) -> None:
        """Single-statement update (auto-commit), applied immediately."""
        from repro.core.storage import (UpdateSlots, apply_updates,
                                        empty_update_batch)
        schema = self.plan.catalog.schemas[table]
        slots = UpdateSlots(1, 1, 1)
        b = empty_update_batch(schema, slots, xp=np)
        if kind == "insert":
            for c, v in payload.items():
                b["ins_rows"][c][0] = int(v)
            b["ins_mask"][0] = True
        elif kind == "update":
            b["upd_key"][0] = int(payload["key"])
            b["upd_col"][0] = schema.columns.index(payload["col"])
            b["upd_val"][0] = int(payload["val"])
            b["upd_mask"][0] = True
        else:
            b["del_key"][0] = int(payload["key"])
            b["del_mask"][0] = True
        self.state = dict(self.state)
        self.state[table] = apply_updates(
            schema, self.state[table], jax.tree.map(jnp.asarray, b))
