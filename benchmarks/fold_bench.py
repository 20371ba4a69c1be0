"""Dynamic plan-folding benchmark (the PR-8 serving record).

The fold contract (core/folding.py) is that admitting a new query
template costs the running clients almost nothing: the extended plan
compiles on a background thread while the OLD compiled heartbeat keeps
serving, and the only beat that pays for the swap is the single forced
full-rescan migration beat.  This bench measures exactly that contract
on the index-less TPC-W plan at the 4096-row acceptance geometry:

  steady      — the pre-fold steady-state delta beat wall (the PR-6
                fused single-launch path, asserted via launch counts);
  during_fold — the SAME trickle beats while the background fold
                builds + jit-warms the extended plan.  The SLA gate
                (tests/test_sla_gate.py) holds their median within
                1.5x of the steady median: folding must not stop — or
                visibly stall — the world;
  migration   — the one full-rescan beat that commits the fold
                (carry migration + reseed under the new layout);
  post_steady — steady beats on the extended plan, back on the single
                fused launch (launch counts asserted again: the swap
                must not knock the engine off the fused path).

``python -m benchmarks.fold_bench`` prints the dict; benchmarks/run.py
folds it into BENCH_PR8.json for the SLA gate.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from repro.core.executor import SharedDBEngine
from repro.core.plan import compile_plan
from repro.workloads import tpcw

SCALE_ITEMS = 4096
SCALE_CUSTOMERS = 2880
N_BASE = 10          # held out and folded in mid-run:
#                      order_lines / order_display / get_cart

CHAINED_OPS = ("scan", "scan_delta", "join_delta", "join_partitioned",
               "join_block")


def _median_us(beats: List) -> float:
    return float(np.median([b.wall_s for b in beats])) * 1e6


def _assert_fused(beats: List, label: str) -> Dict[str, int]:
    ops: Dict[str, int] = {}
    for b in beats:
        for op, n in b.backend_ops.items():
            if n:
                ops[op] = max(ops.get(op, 0), n)
    assert ops.get("fused_delta") == 1, (label, ops)
    assert all(ops.get(op, 0) == 0 for op in CHAINED_OPS), (label, ops)
    return ops


def run(smoke: bool = False, scale_items: int = SCALE_ITEMS) -> Dict:
    import time

    rng = np.random.default_rng(11)
    catalog = tpcw.make_catalog(scale_items, SCALE_CUSTOMERS,
                                dense_pk_index=False)
    templates, caps = tpcw.make_templates(
        catalog.schemas["item"].capacity)
    base = compile_plan(catalog, templates[:N_BASE],
                        {t.name: caps[t.name]
                         for t in templates[:N_BASE]})
    data = tpcw.generate_data(rng, scale_items, SCALE_CUSTOMERS)
    eng = SharedDBEngine(base, tpcw.DEFAULT_UPDATE_SLOTS, data,
                         kernels="jnp")

    def trickle(subs, i):
        eng.submit_update("customer", "update",
                          {"key": int(rng.integers(0, SCALE_CUSTOMERS)),
                           "col": "c_expiration", "val": 13000 + i})
        for name, params in subs:
            eng.submit(name, params)
        return eng.run_until_drained()

    pre = [("get_book", {0: (5, 5)}), ("get_customer", {0: (7, 7)})]
    post = [("order_lines", {0: (10, 10)}), ("get_cart", {0: (12, 12)}),
            ("get_book", {0: (5, 5)})]
    n_steady = 6 if smoke else 12

    for name, params in pre:                 # seed + compile deltas
        eng.submit(name, params)
    eng.run_until_drained()
    for i in range(3):
        trickle(pre, i)
    steady = [b for i in range(n_steady) for b in trickle(pre, 10 + i)
              if b.join_path == "delta"]
    assert steady, "never reached the pre-fold delta-join path"
    pre_ops = _assert_fused(steady, "steady")

    # ---- background fold: the old compiled heartbeat keeps serving
    # while the extended plan builds + jit-warms on the fold thread
    t0 = time.perf_counter()
    eng.begin_fold(templates[N_BASE:],
                   {t.name: caps[t.name] for t in templates[N_BASE:]},
                   background=True)
    # measure a fixed window of beats inside the build (the fold thread
    # runs deniced — serving keeps the cores, the build fills the
    # gaps), then idle so the build can land
    during: List = []
    n_during = 4 if smoke else 8
    while len(during) < n_during and eng.fold_in_flight() \
            and not eng.fold_ready():
        during.extend(b for b in trickle(pre, 100 + len(during))
                      if b.scan_path == "delta")
    beats_during_build = len(during)
    while eng.fold_in_flight() and not eng.fold_ready():
        time.sleep(0.01)
    build_wall_s = time.perf_counter() - t0
    assert during, "fold built before a single beat was served"
    _assert_fused([b for b in during if b.join_path == "delta"],
                  "during_fold")

    # ---- the migration beat: commit + carry migration + full rescan
    mig = trickle(post, 999)
    assert eng.folds_done == 1 and mig[0].scan_path == "full", \
        (eng.folds_done, [b.scan_path for b in mig])

    for i in range(3):                       # compile the post deltas
        trickle(post, 1000 + i)
    post_steady = [b for i in range(n_steady)
                   for b in trickle(post, 1100 + i)
                   if b.join_path == "delta"]
    assert post_steady, "never reached the post-fold delta-join path"
    post_ops = _assert_fused(post_steady, "post_steady")

    steady_us = _median_us(steady)
    during_us = _median_us(during)
    return {
        "scale_items": scale_items,
        "steady_beats": len(steady),
        "steady_us": steady_us,
        "beats_during_build": beats_during_build,
        "during_fold_us": during_us,
        "fold_serving_ratio": during_us / max(steady_us, 1e-9),
        "build_wall_s": build_wall_s,
        "migration_beat_us": mig[0].wall_s * 1e6,
        "post_steady_us": _median_us(post_steady),
        "pre_fold_launches": int(sum(pre_ops.values())),
        "post_fold_launches": int(sum(post_ops.values())),
    }


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    import json
    import sys
    print(json.dumps(run(smoke="--smoke" in sys.argv), indent=2))
