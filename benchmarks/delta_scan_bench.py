"""Incremental-scan benchmarks (the PR-3 perf record).

Two measurements, one kernel-level and one engine-level:

  scan_curve() — the delta scan phase (contiguous admission pane merged
                 by dynamic_update_slice + dirty-row kernel + sorted
                 scatter-back, exactly the composite
                 core/lowering.build_delta_cycle runs per stage) vs the
                 full-rescan compare kernel, at the real TPC-W item
                 stage's window width / pane capacity / dirty capacity,
                 over growing table sizes.  Steady-state shape: one
                 changed admission word, <=1% dirty rows.  Both sides
                 run inside one compiled fori_loop (the carry feeding
                 each iteration, like the real heartbeat chain) so the
                 measurement is per-iteration compute, not python/jit
                 dispatch overhead.
  heartbeat()  — engine-level steady-state heartbeat wall time over the
                 13-template TPC-W plan: trickle admission (one point
                 template) plus two row updates per beat, measured with
                 delta_scans=True vs False; CycleResult.scan_path
                 attributes each heartbeat to its path.

``python -m benchmarks.delta_scan_bench`` prints the dict;
benchmarks/run.py folds it into BENCH_PR3.json, which
tests/test_sla_gate.py gates against stored thresholds.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backends
from repro.core.executor import SharedDBEngine
from repro.core.lowering import lower_plan
from repro.workloads import tpcw


def _best_of(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _delta_scan_fn(backend, w: int, A: int):
    """The build_delta_cycle scan phase as a standalone jittable."""

    def fn(prev, cols, lo, hi, valid, dirty_rows, changed):
        from repro.core.storage import scatter_dirty_rows
        T = cols.shape[1]
        wch = jnp.any(changed.reshape(w, 32), axis=1)
        w0 = jnp.minimum(jnp.argmax(wch).astype(jnp.int32), w - A)
        lo_a = jax.lax.dynamic_slice(lo, (0, w0 * 32),
                                     (lo.shape[0], A * 32))
        hi_a = jax.lax.dynamic_slice(hi, (0, w0 * 32),
                                     (hi.shape[0], A * 32))
        pane = backend.scan(cols, lo_a, hi_a, valid)
        m = jax.lax.dynamic_update_slice(prev, pane, (0, w0))
        dwords = backend.scan_delta(cols, lo, hi, valid, dirty_rows)
        return scatter_dirty_rows(m, dirty_rows, dwords, T)

    return fn


def scan_curve(sizes=(1024, 4096), reps: int = 5,
               iters: int = 40) -> List[Dict]:
    """Delta vs full-rescan scan phase at the TPC-W item stage shape."""
    be = backends.get_backend("jnp")
    # the real stage geometry: window width, pane capacity, dirty cap
    plan = tpcw.build_tpcw_plan(1000, 2880)
    st = next(s for s in lower_plan(plan).scans if s.table == "item")
    w, A = st.whi - st.wlo, st.delta_words
    C, Q = len(st.cols), st.q_window
    D = plan.catalog.schemas["item"].dirty_cap
    out = []
    for T in sizes:
        rng = np.random.default_rng(T)
        cols0 = jnp.asarray(rng.integers(0, T, (C, T)), jnp.int32)
        lo = jnp.asarray(rng.integers(0, T, (C, Q)), jnp.int32)
        hi = lo + jnp.asarray(rng.integers(0, T // 8, (C, Q)), jnp.int32)
        valid = jnp.asarray(rng.random(T) > 0.05)
        # steady state: one changed admission word, <=1% dirty rows
        changed = np.zeros(Q, bool)
        changed[64:72] = True
        n_dirty = max(1, T // 100)
        dirty = np.full(D, T, np.int64)
        dirty[:n_dirty] = np.sort(rng.choice(T, n_dirty, replace=False))
        dirty_j = jnp.asarray(dirty, jnp.int32)
        changed_j = jnp.asarray(changed)

        delta_step = _delta_scan_fn(be, w, A)
        prev = jax.jit(be.scan)(cols0, lo, hi, valid)
        # the delta phase must reproduce the full rescan bit-for-bit
        got = delta_step(prev, cols0, lo, hi, valid, dirty_j, changed_j)
        assert (np.asarray(got) == np.asarray(prev)).all()

        # measure inside one compiled loop, each iteration consuming the
        # previous mask (the real carry chain) so nothing hoists out
        def chained(step):
            def body(_, m):
                cols = cols0 + (m[0, 0] & jnp.uint32(0)).astype(jnp.int32)
                return step(m, cols)
            return jax.jit(
                lambda: jax.lax.fori_loop(0, iters, body, prev))

        loop_full = chained(lambda m, cols: be.scan(cols, lo, hi, valid))
        loop_delta = chained(lambda m, cols: delta_step(
            m, cols, lo, hi, valid, dirty_j, changed_j))
        jax.block_until_ready(loop_full())               # compile
        jax.block_until_ready(loop_delta())
        # alternate sides per rep so machine drift hits both equally
        t_full = t_delta = float("inf")
        for _ in range(reps):
            t_full = min(t_full, _best_of(loop_full, 1))
            t_delta = min(t_delta, _best_of(loop_delta, 1))
        t_full /= iters
        t_delta /= iters
        out.append({"rows": T, "q_window": Q, "pane_words": A,
                    "dirty_rows": n_dirty,
                    "full_us": t_full * 1e6, "delta_us": t_delta * 1e6,
                    "speedup": t_full / max(t_delta, 1e-12)})
    return out


def heartbeat(scale_items: int = 4096, beats: int = 30,
              reps: int = 3) -> Dict:
    """Steady-state heartbeat wall time, delta vs forced full rescan.

    Both engines are driven INTERLEAVED, beat for beat, so machine drift
    during the run lands on both sides equally (sequential runs showed
    up to 2x apparent skew from contention alone on shared CPUs)."""
    rng = np.random.default_rng(9)
    plan = tpcw.build_tpcw_plan(scale_items, 2880)
    data = tpcw.generate_data(rng, scale_items, 2880)
    engines = {}
    for label, delta_scans in (("delta", True), ("full", False)):
        eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data,
                             delta_scans=delta_scans)
        eng.submit("get_book", {0: (1, 1)})
        eng.run_until_drained()                          # compiles full
        for _ in range(2):       # two slot-stable beats: the second is
            # delta-eligible, so this compiles the delta cycle too —
            # keeping BOTH paths' jit cost out of the measured loop
            eng.submit_update("item", "update",
                              {"key": 1, "col": "i_cost", "val": 1})
            eng.submit("admin_item", {0: (1, 1)})
            eng.run_until_drained()
        engines[label] = eng
    walls = {label: [] for label in engines}
    paths = {label: {"delta": 0, "full": 0, "mixed": 0}
             for label in engines}
    for _ in range(reps):
        for i in range(beats):
            k = int(rng.integers(0, scale_items))
            v = int(rng.integers(100, 9999))
            for label, eng in engines.items():
                eng.submit("admin_item", {0: (k, k)})
                eng.submit_update("item", "update",
                                  {"key": k, "col": "i_cost", "val": v})
                eng.submit_update("item", "update",
                                  {"key": (k + 7) % scale_items,
                                   "col": "i_stock", "val": 9})
                done = eng.run_until_drained(max_cycles=4)
                walls[label].extend(d.wall_s for d in done)
                for d in done:
                    paths[label][d.scan_path or "full"] += 1
    d_eng = engines["delta"]
    total = max(d_eng.delta_cycles + d_eng.full_cycles, 1)
    d_us = float(np.mean(walls["delta"])) * 1e6
    f_us = float(np.mean(walls["full"])) * 1e6
    return {"scale_items": scale_items, "beats": beats * reps,
            "delta_heartbeat_us": d_us,
            "full_heartbeat_us": f_us,
            "heartbeat_speedup": f_us / max(d_us, 1e-9),
            "delta_cycle_fraction": d_eng.delta_cycles / total,
            "paths_delta_engine": paths["delta"],
            "paths_full_engine": paths["full"]}


def run(smoke: bool = False) -> Dict:
    return {
        "curve": scan_curve(sizes=(1024, 4096),
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
