"""Delta-aware join benchmarks (the PR-4 perf record).

Two measurements, one kernel-level and one engine-level:

  join_curve() — the delta join phase (dirty-row probe via the
                 ``join_delta`` backend op + sorted-scatter merge into
                 the carried rid array + the bitmask intersection,
                 exactly what core/lowering's delta-join post_scan runs
                 per stage) vs the full partitioned re-probe, at the
                 TPC-W window width and partition layout, over growing
                 table sizes.  Steady-state shape: <=1% dirty spine
                 rows, PK side untouched.  Both sides run inside one
                 compiled fori_loop (the rid carry feeding each
                 iteration, like the real heartbeat chain) so the
                 measurement is per-iteration compute, not python/jit
                 dispatch overhead.
  heartbeat()  — engine-level steady-state heartbeat wall time over the
                 index-less TPC-W plan (every join partitioned):
                 slot-stable trickle admission plus one spine-side
                 (customer) update per beat, measured with
                 delta_joins=True vs False (delta SCANS on for both, so
                 the difference isolates the join phase);
                 CycleResult.join_path attributes each heartbeat.

``python -m benchmarks.delta_join_bench`` prints the dict;
benchmarks/run.py folds it into BENCH_PR4.json, which
tests/test_sla_gate.py gates against stored thresholds.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backends
from repro.core.lowering import lower_plan, partition_layout
from repro.core.storage import build_key_partitions, scatter_dirty_rows
from repro.workloads import tpcw


def _best_of(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _combined(rid, mask_l, mask_r):
    safe = jnp.clip(rid, 0, mask_r.shape[0] - 1)
    return jnp.where((rid >= 0)[:, None], mask_l & mask_r[safe],
                     jnp.uint32(0))


def _delta_join_fn(backend):
    """The delta-join stage phase as a standalone jittable."""

    def fn(rid_carry, keys_l, mask_l, parts, mask_r, dirty):
        T = keys_l.shape[0]
        bkeys, brows, bounds = parts
        rid_d = backend.join_delta(keys_l, dirty, bkeys, brows, bounds)
        rid = scatter_dirty_rows(rid_carry, dirty, rid_d, T)
        return rid, _combined(rid, mask_l, mask_r)

    return fn


def join_curve(sizes=(1024, 4096), reps: int = 5,
               iters: int = 40) -> List[Dict]:
    """Delta vs full partitioned probe at the TPC-W window width."""
    be = backends.get_backend("jnp")
    # the real plan geometry: window width in words, dirty capacity
    plan = tpcw.build_tpcw_plan(4096, 2880, dense_pk_index=False)
    W = lower_plan(plan).W
    D = plan.catalog.schemas["order_line"].dirty_cap
    out = []
    for T in sizes:
        rng = np.random.default_rng(T)
        n_parts, bucket_cap = partition_layout(T)
        keys_r = jnp.asarray(rng.permutation(T * 2)[:T], jnp.int32)
        valid_r = jnp.asarray(rng.random(T) > 0.05)
        keys_l0 = jnp.asarray(rng.integers(0, T * 2, T), jnp.int32)
        mask_l = jnp.asarray(rng.integers(0, 2**32, (T, W)), jnp.uint32)
        mask_r = jnp.asarray(rng.integers(0, 2**32, (T, W)), jnp.uint32)
        parts = build_key_partitions(keys_r, valid_r, n_parts, bucket_cap)
        # steady state: <=1% dirty spine rows, PK side untouched
        n_dirty = max(1, T // 100)
        dirty = np.full(D, T, np.int64)
        dirty[:n_dirty] = np.sort(rng.choice(T, n_dirty, replace=False))
        dirty_j = jnp.asarray(dirty, jnp.int32)

        delta_step = _delta_join_fn(be)
        rid0, comb0 = jax.jit(be.join_partitioned)(keys_l0, mask_l,
                                                   *parts, mask_r)
        # the delta phase must reproduce the full probe bit-for-bit
        rid1, comb1 = delta_step(rid0, keys_l0, mask_l, parts, mask_r,
                                 dirty_j)
        assert (np.asarray(rid1) == np.asarray(rid0)).all()
        assert (np.asarray(comb1) == np.asarray(comb0)).all()

        # measure inside one compiled loop, each iteration consuming the
        # previous rid (the real carry chain) so nothing hoists out
        def chained(step):
            def body(_, rid):
                keys_l = keys_l0 + (rid[0] & jnp.int32(0))
                return step(rid, keys_l)
            return jax.jit(
                lambda: jax.lax.fori_loop(0, iters, body, rid0))

        loop_full = chained(
            lambda rid, keys_l: be.join_partitioned(
                keys_l, mask_l, *parts, mask_r)[0])
        loop_delta = chained(
            lambda rid, keys_l: delta_step(
                rid, keys_l, mask_l, parts, mask_r, dirty_j)[0])
        jax.block_until_ready(loop_full())               # compile
        jax.block_until_ready(loop_delta())
        # alternate sides per rep so machine drift hits both equally
        t_full = t_delta = float("inf")
        for _ in range(reps):
            t_full = min(t_full, _best_of(loop_full, 1))
            t_delta = min(t_delta, _best_of(loop_delta, 1))
        t_full /= iters
        t_delta /= iters
        out.append({"rows": T, "w_words": W,
                    "n_partitions": n_parts, "bucket_cap": bucket_cap,
                    "dirty_rows": n_dirty,
                    "full_us": t_full * 1e6, "delta_us": t_delta * 1e6,
                    "speedup": t_full / max(t_delta, 1e-12)})
    return out


def heartbeat(scale_items: int = 4096, beats: int = 30,
              reps: int = 3) -> Dict:
    """Steady-state heartbeat wall time, delta joins vs forced full
    probes (delta scans ON for both sides, isolating the join phase).

    Both engines are driven INTERLEAVED, beat for beat, so machine drift
    during the run lands on both sides equally."""
    from repro.core.executor import SharedDBEngine

    rng = np.random.default_rng(11)
    plan = tpcw.build_tpcw_plan(scale_items, 2880, dense_pk_index=False)
    data = tpcw.generate_data(rng, scale_items, 2880)
    engines = {}
    for label, delta_joins in (("delta", True), ("full", False)):
        eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data,
                             delta_joins=delta_joins)
        eng.submit("get_book", {0: (1, 1)})
        eng.run_until_drained()                          # compiles full
        for _ in range(2):       # two slot-stable beats: the second is
            # delta-eligible, compiling the delta(-join) cycle too —
            # keeping every path's jit cost out of the measured loop
            eng.submit_update("customer", "update",
                              {"key": 1, "col": "c_expiration",
                               "val": 13000})
            eng.submit("get_book", {0: (1, 1)})
            eng.run_until_drained()
        engines[label] = eng
    walls = {label: [] for label in engines}
    join_paths = {label: {"delta": 0, "full": 0, "mixed": 0}
                  for label in engines}
    for _ in range(reps):
        for i in range(beats):
            k = int(rng.integers(0, scale_items))
            c = int(rng.integers(0, 2880))
            v = int(rng.integers(12000, 15000))
            for label, eng in engines.items():
                eng.submit("get_book", {0: (k, k)})
                eng.submit_update("customer", "update",
                                  {"key": c, "col": "c_expiration",
                                   "val": v})
                done = eng.run_until_drained(max_cycles=4)
                walls[label].extend(d.wall_s for d in done)
                for d in done:
                    join_paths[label][d.join_path or "full"] += 1
    d_eng = engines["delta"]
    total = max(d_eng.delta_join_cycles + d_eng.full_join_cycles, 1)
    d_us = float(np.mean(walls["delta"])) * 1e6
    f_us = float(np.mean(walls["full"])) * 1e6
    return {"scale_items": scale_items, "beats": beats * reps,
            "delta_heartbeat_us": d_us,
            "full_heartbeat_us": f_us,
            "heartbeat_speedup": f_us / max(d_us, 1e-9),
            "delta_join_fraction": d_eng.delta_join_cycles / total,
            "join_paths_delta_engine": join_paths["delta"],
            "join_paths_full_engine": join_paths["full"]}


def run(smoke: bool = False) -> Dict:
    return {
        "curve": join_curve(sizes=(1024, 4096),
                            reps=3 if smoke else 5),
        "heartbeat": heartbeat(beats=15 if smoke else 30,
                               reps=1 if smoke else 3),
    }


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    import json
    import sys
    print(json.dumps(run(smoke="--smoke" in sys.argv), indent=2))
