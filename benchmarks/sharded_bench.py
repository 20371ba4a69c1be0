"""Sharded reseed-beat benchmark (the PR-5 perf record).

The delta beats made the steady state cheap (PR 3/4); what remains on
the critical path is the full-rescan / reseed beat — the bounded worst
case every overflow or admission-churn heartbeat pays — and that is
exactly what row-range sharding scatters across the mesh
(core/sharding.py).  Two measurements:

  per_device() — the reseed scan work ONE device pays, before vs after
                 sharding: the full item-stage compare at the padded
                 table height ``Tp`` vs the per-shard slice height
                 ``Ts = Tp / S`` taken from the real ``ShardSpec`` of
                 the plan.  Both run identically on one device in a
                 compiled sequence, so the ratio is deterministic on
                 any CI host — this is the quantity a real mesh (one
                 shard per chip, the paper's one-operator-per-core
                 scaling, §4.5) converts into wall-clock, and the gate
                 trips if the sharded lowering ever stops splitting the
                 row ranges.
  engine_beats() — context: wall time of the engine-level reseed beat
                 on a 1-shard vs multi-shard mesh of FORCED host CPU
                 devices, plus the sharded steady-state delta beat and
                 its path fractions.  On a 2-core CI host the forced
                 devices time-slice the same cores and XLA:CPU already
                 multi-threads the single-device op, so these walls
                 measure overhead honesty (ceilings + the delta paths
                 still engaging), not the mesh speedup.

``benchmarks/run.py`` runs it in a SUBPROCESS on forced host CPU
devices (it sets ``JAX_PLATFORMS=cpu`` and
``--xla_force_host_platform_device_count=8`` for the child only), so
the PR-3/4 records keep measuring on the plain single-device client.
Run alone it uses whatever devices JAX finds:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python -m benchmarks.sharded_bench [--smoke]   # prints JSON record

``run.py`` folds the record into ``BENCH_PR5.json``;
``tests/test_sla_gate.py`` gates it against stored thresholds.
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

SCALE_ITEMS = 4096
SHARDS = 4


def _timeit(f, args, n=20, reps=4) -> float:
    import jax
    jax.block_until_ready(f(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(n):
            out = f(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / n)
    return best


def per_device(scale_items: int = SCALE_ITEMS,
               shards: int = SHARDS) -> Dict:
    """Reseed scan cost one device pays: full stage height vs the
    per-shard slice, at the REAL plan's item-stage geometry."""
    import jax
    import jax.numpy as jnp
    from repro.core import backends
    from repro.core.lowering import lower_plan
    from repro.core.sharding import build_shard_spec, make_row_mesh
    from repro.workloads import tpcw

    be = backends.get_backend("jnp")
    plan = tpcw.build_tpcw_plan(scale_items, 2880, dense_pk_index=False)
    spec = build_shard_spec(plan, make_row_mesh(shards))
    st = next(s for s in lower_plan(plan).scans if s.table == "item")
    C, Q = len(st.cols), st.q_window
    Tp, Ts = spec.padded["item"], spec.shard_rows["item"]
    rng = np.random.default_rng(0)
    lo = jnp.asarray(rng.integers(0, 5000, (C, Q)), jnp.int32)
    hi = lo + 2000

    def scan_at(T: int) -> float:
        cols = jnp.asarray(rng.integers(0, 10000, (C, T)), jnp.int32)
        valid = jnp.asarray(rng.random(T) > 0.05)
        f = jax.jit(lambda c, v: be.scan(c, lo, hi, v))
        return _timeit(f, (cols, valid))

    full_us = scan_at(Tp) * 1e6
    shard_us = scan_at(Ts) * 1e6
    return {"table": "item", "rows_full": Tp, "rows_shard": Ts,
            "cols": C, "q_window": Q, "shards": shards,
            "full_scan_us": full_us, "shard_scan_us": shard_us,
            "speedup": full_us / max(shard_us, 1e-9)}


def engine_beats(scale_items: int = SCALE_ITEMS, shards: int = SHARDS,
                 beats: int = 8, warmup: int = 2) -> Dict:
    """Engine-level context on forced host devices: reseed beat walls
    (1-shard vs sharded mesh, interleaved beat-for-beat) and the
    sharded steady-state delta beat with its path fractions."""
    import jax
    from repro.core.executor import SharedDBEngine
    from repro.core.sharding import make_row_mesh
    from repro.workloads import tpcw

    rng = np.random.default_rng(11)
    plan = tpcw.build_tpcw_plan(scale_items, 2880, dense_pk_index=False)
    data = tpcw.generate_data(rng, scale_items, 2880)
    engines = {}
    for label, n in (("single", 1), ("sharded", shards)):
        eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data,
                             delta_scans=False, delta_joins=False,
                             mesh=make_row_mesh(n))
        for _ in range(warmup):                          # compile + warm
            eng.submit("get_book", {0: (1, 1)})
            eng.run_until_drained()
        engines[label] = eng
    walls = {label: [] for label in engines}
    for i in range(beats):
        k = int(rng.integers(0, scale_items))
        c = int(rng.integers(0, 2880))
        for label, eng in engines.items():
            eng.submit("get_book", {0: (k, k)})
            eng.submit_update("customer", "update",
                              {"key": c, "col": "c_expiration",
                               "val": 13000 + i})
            done = eng.run_until_drained(max_cycles=4)
            assert all(d.scan_path == "full" for d in done)
            walls[label].extend(d.wall_s for d in done)

    # steady-state delta beats: the SAME trickle stream on the sharded
    # mesh and on a single device, so the end-to-end sharded/single
    # delta-beat ratio is apples-to-apples inside this one forced-host
    # subprocess.  With the PR-6 on-device cross-shard merge, collect()
    # no longer pays a host-side key-merge, so the ratio measures
    # shard_map dispatch overhead (bounded by the SLA gate) rather than
    # a host merge that grows with the result surface.
    def delta_walls(mesh):
        drng = np.random.default_rng(13)
        eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data,
                             mesh=mesh)
        eng.submit("get_book", {0: (1, 1)})
        eng.run_until_drained()                           # seed (full)
        for i in range(2):                                # compile delta
            eng.submit_update("customer", "update",
                              {"key": 1, "col": "c_expiration",
                               "val": 13000 + i})
            eng.submit("get_book", {0: (1, 1)})
            eng.run_until_drained()
        dwalls = []
        for i in range(beats):
            k = int(drng.integers(0, scale_items))
            c = int(drng.integers(0, 2880))
            eng.submit("get_book", {0: (k, k)})
            eng.submit_update("customer", "update",
                              {"key": c, "col": "c_expiration",
                               "val": 14000 + i})
            dwalls.extend(d.wall_s
                          for d in eng.run_until_drained(max_cycles=4))
        return eng, dwalls

    eng, dwalls = delta_walls(make_row_mesh(shards))
    _, dwalls_single = delta_walls(None)
    total = max(eng.delta_cycles + eng.full_cycles, 1)
    sharded_delta_us = float(np.mean(dwalls)) * 1e6
    single_delta_us = float(np.mean(dwalls_single)) * 1e6
    return {"scale_items": scale_items, "shards": shards,
            "beats": beats,
            "devices_forced": jax.default_backend() == "cpu",
            "single_reseed_us": float(np.mean(walls["single"])) * 1e6,
            "sharded_reseed_us": float(np.mean(walls["sharded"])) * 1e6,
            "delta_heartbeat_us": sharded_delta_us,
            "single_delta_heartbeat_us": single_delta_us,
            "sharded_delta_ratio": sharded_delta_us
            / max(single_delta_us, 1e-9),
            "delta_cycle_fraction": eng.delta_cycles / total,
            "delta_join_fraction": eng.delta_join_cycles
            / max(eng.delta_join_cycles + eng.full_join_cycles, 1)}


def run(smoke: bool = False) -> Dict:
    return {"per_device": per_device(),
            "engine": engine_beats(beats=6 if smoke else 12)}


if __name__ == "__main__":
    import json
    import sys

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(json.dumps(run(smoke="--smoke" in sys.argv), indent=2))
