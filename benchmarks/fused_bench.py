"""Fused delta-heartbeat benchmark (the PR-6 perf record).

The PR-4/5 steady state chained one backend launch per delta unit —
pane recompute + dirty re-scan per predicated stage, dirty probe +
rid merge per carried join.  PR 6 fuses the whole delta path into ONE
``backend.fused_delta`` launch (kernels/fused_delta.py), so the
measurement is engine-level and beat-for-beat:

  heartbeat() — steady-state trickle beats on the index-less TPC-W
                plan at the 4096-row acceptance geometry, fused engine
                vs the CHAINED engine (the same jnp operator backend
                with ``fused_delta=None``, which drops the lowering
                back onto the per-unit op chain).  Both engines admit
                the identical update + query stream, interleaved per
                beat so host noise hits both sides alike.  Each side
                reports the per-phase wall breakdown the executor now
                records (staging / dispatch / kernel / collect) and
                the per-beat backend-op launch counts — the fused side
                must show exactly ONE fused_delta op and ZERO chained
                delta ops, asserted here so the record can never show
                a stale path.

  delta_phase() — the fused work itself (every predicated stage's
                  pane + dirty rescan, every carried join's probe)
                  measured inside one compiled carry chain at the real
                  lowered geometry, fused op vs the chained op
                  sequence.  The beat wall above is dominated by the
                  full-width group-by/sort post stages that run
                  identically on both sides, so THIS is where the
                  fusion win is measurable on a noisy host.

The record also carries the ANALYTIC roofline footprint of one fused
beat (roofline/analysis.fused_delta_footprint): bytes moved, integer
compare-ops, and which roofline term dominates on the target part.

``python -m benchmarks.fused_bench`` prints the dict;
benchmarks/run.py folds it into BENCH_PR6.json, which
tests/test_sla_gate.py gates against stored thresholds.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from repro.core import backends
from repro.core.executor import SharedDBEngine
from repro.core.lowering import lower_plan
from repro.roofline.analysis import fused_delta_footprint
from repro.workloads import tpcw

SCALE_ITEMS = 4096
SCALE_CUSTOMERS = 2880

CHAINED_OPS = ("scan", "scan_delta", "join_delta", "join_partitioned",
               "join_block")


def _chained_backend_name() -> str:
    """The jnp backend with the fused op removed: the lowering then
    emits the PR-4/5 chained delta path, everything else identical."""
    name = "jnp-chained"
    if name not in backends.available_backends():
        backends.register_backend(dataclasses.replace(
            backends.get_backend("jnp"), name=name, fused_delta=None))
    return name


def _phase_means(beats: List) -> Dict[str, float]:
    return {
        "wall_us": float(np.mean([b.wall_s for b in beats])) * 1e6,
        "stage_us": float(np.mean([b.t_stage_s for b in beats])) * 1e6,
        "dispatch_us": float(np.mean([b.t_dispatch_s
                                      for b in beats])) * 1e6,
        "kernel_us": float(np.mean([b.t_kernel_s for b in beats])) * 1e6,
        "collect_us": float(np.mean([b.t_collect_s
                                     for b in beats])) * 1e6,
    }


def heartbeat(scale_items: int = SCALE_ITEMS, beats: int = 10,
              warmup: int = 3) -> Dict:
    """Fused vs chained steady-state delta beat, interleaved."""
    rng = np.random.default_rng(4)
    plan = tpcw.build_tpcw_plan(scale_items, SCALE_CUSTOMERS,
                                dense_pk_index=False)
    data = tpcw.generate_data(rng, scale_items, SCALE_CUSTOMERS)
    engines = {
        "fused": SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data,
                                kernels="jnp"),
        "chained": SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data,
                                  kernels=_chained_backend_name()),
    }

    def trickle(eng, i):
        eng.submit_update("customer", "update",
                          {"key": int(rng.integers(0, SCALE_CUSTOMERS)),
                           "col": "c_expiration", "val": 13000 + i})
        eng.submit("order_lines", {0: (10, 10)})
        eng.submit("get_cart", {0: (12, 12)})
        eng.submit("get_book", {0: (5, 5)})
        return eng.run_until_drained()

    for eng in engines.values():                 # seed + compile deltas
        eng.submit("order_lines", {0: (10, 10)})
        eng.submit("get_cart", {0: (12, 12)})
        eng.submit("get_book", {0: (5, 5)})
        eng.run_until_drained()
        for i in range(warmup):
            trickle(eng, i)
    steady = {label: [] for label in engines}
    for i in range(beats):
        for label, eng in engines.items():       # interleaved beats
            steady[label].extend(b for b in trickle(eng, 100 + i)
                                 if b.join_path == "delta")
    record = {"scale_items": scale_items, "beats": beats}
    for label, bs in steady.items():
        assert bs, f"{label} engine never reached the delta-join path"
        ops: Dict[str, int] = {}
        for b in bs:
            for op, n in b.backend_ops.items():
                if n:
                    ops[op] = max(ops.get(op, 0), n)
        record[label] = {**_phase_means(bs), "backend_ops_per_beat": ops,
                         "delta_beats": len(bs)}
    fused_ops = record["fused"]["backend_ops_per_beat"]
    assert fused_ops.get("fused_delta") == 1, fused_ops
    assert all(fused_ops.get(op, 0) == 0 for op in CHAINED_OPS), \
        fused_ops
    chained_ops = record["chained"]["backend_ops_per_beat"]
    assert chained_ops.get("fused_delta", 0) == 0, chained_ops
    record["fused_vs_chained"] = (record["fused"]["wall_us"]
                                  / max(record["chained"]["wall_us"],
                                        1e-9))
    record["chained_launches"] = int(sum(chained_ops.values()))
    record["fused_launches"] = int(
        sum(fused_ops.values()))             # fused_delta + post groupbys
    return record


def delta_phase(reps: int = 5, iters: int = 40) -> Dict:
    """The fused work itself, fused op vs chained op sequence, measured
    inside one compiled carry chain at the real lowered TPC-W geometry.

    The engine-level beat wall at the acceptance scale is dominated by
    the full-width group-by/sort post stages (see the PR-3 perf table:
    "scan is not the bottleneck at this scale"), which run identically
    on both sides — so ``heartbeat()``'s wall ratio sits at ~1.0 inside
    host noise.  This is the apples-to-apples measurement of the path
    PR 6 actually fuses, at the steady-state trickle shape (ONE changed
    admission pane, ONE dirty table, ONE dirty-spine join, every other
    stage idle): the chained path re-runs every stage's pane recompute
    + dirty rescan and every carried join's probe with empty inputs —
    exactly what the chained delta cycle compiles — while the fused op
    cond-skips them (identities on the carry, kernels/ref.py).
    """
    import jax
    import jax.numpy as jnp

    from repro.core.lowering import INT_MIN, partition_layout
    from repro.core.storage import build_key_partitions, scatter_dirty_rows

    be = backends.get_backend("jnp")
    rng = np.random.default_rng(6)
    plan = tpcw.build_tpcw_plan(SCALE_ITEMS, SCALE_CUSTOMERS,
                                dense_pk_index=False)
    lowered = lower_plan(plan)
    schemas = plan.catalog.schemas

    scan_in = []
    for k, st in enumerate(s for s in lowered.scans if s.cols):
        T, D = schemas[st.table].capacity, schemas[st.table].dirty_cap
        C, Q, A = len(st.cols), st.q_window, st.delta_words
        cols = jnp.asarray(rng.integers(0, T, (C, T)), jnp.int32)
        lo = jnp.asarray(rng.integers(0, T, (C, Q)), jnp.int32)
        hi = lo + jnp.asarray(rng.integers(0, T // 8, (C, Q)), jnp.int32)
        valid = jnp.asarray(rng.random(T) > 0.05)
        carry = jax.jit(be.scan)(cols, lo, hi, valid)
        rows = np.full(D, T, np.int64)
        n_dirty = max(1, T // 100)
        if k == 1:                     # steady state: ONE dirty table,
            rows[:n_dirty] = np.sort(  # ONE stage's admission changed
                rng.choice(T, n_dirty, replace=False))
        scan_in.append(backends.FusedScanIn(
            cols=cols, lo=lo, hi=hi,
            lo_p=lo[:, :A * 32], hi_p=hi[:, :A * 32], valid=valid,
            carry=carry, w0=jnp.int32(0),
            span=jnp.int32(1 if k == 0 else 0),
            rows=jnp.asarray(rows, jnp.int32),
            dn=jnp.int32(n_dirty if k == 1 else 0)))

    join_in = []
    for k, j in enumerate(jj for jj in lowered.joins
                          if jj.kind != "gather"):
        Tl, Tr = schemas[j.spine].capacity, schemas[j.pk_table].capacity
        Dl = schemas[j.spine].dirty_cap
        keys = jnp.asarray(rng.integers(0, Tr * 2, Tl), jnp.int32)
        keys_r = jnp.asarray(rng.permutation(Tr * 2)[:Tr], jnp.int32)
        valid_r = jnp.asarray(rng.random(Tr) > 0.05)
        if j.kind == "partitioned":
            bkeys, brows, bounds = build_key_partitions(
                keys_r, valid_r, *partition_layout(Tr))
        else:                          # block: one-bucket pseudo-parts
            from repro.core.storage import INT_SENTINEL
            bkeys = jnp.where(valid_r, keys_r, INT_SENTINEL)[None, :]
            brows = jnp.where(valid_r,
                              jnp.arange(Tr, dtype=jnp.int32), -1)[None, :]
            bounds = jnp.full((1,), INT_MIN, jnp.int32)
        rows = np.full(Dl, Tl, np.int64)
        n_dirty = max(1, Tl // 100)
        if k == 0:                     # ONE join's spine dirty
            rows[:n_dirty] = np.sort(
                rng.choice(Tl, n_dirty, replace=False))
        rid0 = jnp.max(jnp.where(
            (bkeys[jnp.clip(jnp.searchsorted(
                bounds, keys, side="right").astype(jnp.int32) - 1,
                0, bounds.shape[0] - 1)] == keys[:, None]),
            brows[jnp.clip(jnp.searchsorted(
                bounds, keys, side="right").astype(jnp.int32) - 1,
                0, bounds.shape[0] - 1)], -1), axis=1)
        join_in.append(backends.FusedJoinIn(
            keys=keys, rows=jnp.asarray(rows, jnp.int32),
            dn=jnp.int32(n_dirty if k == 0 else 0),
            bkeys=bkeys, brows=brows, bounds=bounds, rid_carry=rid0))

    def chained_step(scan_in, join_in):
        """What build_delta_cycle compiles WITHOUT the fused op: every
        stage's pane + dirty rescan, every join's dirty probe."""
        words, rids = [], []
        for e in scan_in:
            T = e.cols.shape[1]
            pane = be.scan(e.cols, e.lo_p, e.hi_p, e.valid)
            m = jax.lax.dynamic_update_slice(e.carry, pane, (0, e.w0))
            dw = be.scan_delta(e.cols, e.lo, e.hi, e.valid, e.rows)
            words.append(scatter_dirty_rows(m, e.rows, dw, T))
        for e in join_in:
            rd = be.join_delta(e.keys, e.rows, e.bkeys, e.brows, e.bounds)
            rids.append(scatter_dirty_rows(e.rid_carry, e.rows, rd,
                                           e.keys.shape[0]))
        return tuple(words), tuple(rids)

    # both sides must be identities on the steady-state carry
    wf, rf = jax.jit(be.fused_delta)(tuple(scan_in), tuple(join_in))
    wc, rc = jax.jit(chained_step)(tuple(scan_in), tuple(join_in))
    for a, b, e in zip(wf, wc, scan_in):
        assert (np.asarray(a) == np.asarray(b)).all()
        assert (np.asarray(a) == np.asarray(e.carry)).all()
    for a, b, e in zip(rf, rc, join_in):
        assert (np.asarray(a) == np.asarray(b)).all()
        assert (np.asarray(a) == np.asarray(e.rid_carry)).all()

    def loop(step):
        # thread a dependency through every stage's inputs so nothing
        # hoists out of the measured carry chain
        def body(_, m):
            p = (m[0, 0] & jnp.uint32(0)).astype(jnp.int32)
            s_in = tuple(e._replace(cols=e.cols + p) for e in scan_in)
            j_in = tuple(e._replace(keys=e.keys + p) for e in join_in)
            words, rids = step(s_in, j_in)
            dep = sum((w[0, 0] & jnp.uint32(0) for w in words[1:]),
                      jnp.uint32(0))
            dep += sum((r[0] & 0 for r in rids), 0).astype(jnp.uint32)
            return words[0] ^ dep
        return jax.jit(lambda: jax.lax.fori_loop(
            0, iters, body, scan_in[0].carry))

    loop_f, loop_c = loop(be.fused_delta), loop(chained_step)
    jax.block_until_ready(loop_f())                        # compile
    jax.block_until_ready(loop_c())
    t_f = t_c = float("inf")
    for _ in range(reps):          # alternate sides so drift cancels
        t_f = min(t_f, _best_of_phase(loop_f))
        t_c = min(t_c, _best_of_phase(loop_c))
    t_f /= iters
    t_c /= iters
    return {"scan_stages": len(scan_in), "joins": len(join_in),
            "chained_us": t_c * 1e6, "fused_us": t_f * 1e6,
            "speedup": t_c / max(t_f, 1e-12)}


def _best_of_phase(fn) -> float:
    import time

    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return time.perf_counter() - t0


def run(smoke: bool = False) -> Dict:
    rec = heartbeat(beats=6 if smoke else 12)
    rec["delta_phase"] = delta_phase()
    lowered = lower_plan(tpcw.build_tpcw_plan(SCALE_ITEMS,
                                              SCALE_CUSTOMERS,
                                              dense_pk_index=False))
    rec["roofline"] = fused_delta_footprint(lowered)
    return rec


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    import json
    import sys
    print(json.dumps(run(smoke="--smoke" in sys.argv), indent=2))
