"""Fig. 10: batch completion time, heavy vs light queries.

Light = ProductDetail's get_book (PK join, 1 row).  Heavy = BestSellers
(3-table join + group-by + top-50).  SharedDB executes a batch in O(cycles)
with bounded per-cycle work; query-at-a-time grows linearly.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common

INT_MAX = 2147483647


def _batch(gen, template: str, n: int):
    items = []
    for _ in range(n):
        if template == "get_book":
            i = int(gen.rng.integers(0, gen.n_items))
            items.append(("get_book", {0: (i, i)}))
        else:
            lo = max(0, gen._next_order - 3333)
            subj = int(gen.rng.integers(0, 24))
            items.append(("best_sellers",
                          {0: (lo, INT_MAX), 1: (subj, subj)}))
    return items


def run(sizes=(1, 4, 16, 64, 256), seed=11):
    rng = np.random.default_rng(seed)
    plan, shared, baseline, gen = common.build_engines(rng)
    common.warmup(shared, baseline, gen)
    rows = []
    for template in ("get_book", "best_sellers"):
        for n in sizes:
            items = _batch(gen, template, n)
            t0 = time.time()
            for name, params in items:
                shared.submit(name, params)
            shared.run_until_drained()
            t_shared = time.time() - t0
            t0 = time.time()
            baseline.execute_batch(items)
            t_base = time.time() - t0
            rows.append((template, n, t_shared, t_base))
            print(f"fig10 {template:12s} batch={n:4d}  "
                  f"shared={t_shared*1e3:8.1f}ms  "
                  f"qaat={t_base*1e3:8.1f}ms  "
                  f"speedup={t_base/max(t_shared,1e-9):5.2f}x", flush=True)
    return rows


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
