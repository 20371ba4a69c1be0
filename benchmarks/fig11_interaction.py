"""Fig. 11: load interaction — heavy queries must not starve light ones.

Fixed light load (get_book) + rising heavy load (best_sellers).  In
SharedDB both share the item/author scans and the plan's bounded cycles, so
light-query goodput stays flat; query-at-a-time head-of-line-blocks.
"""
from __future__ import annotations

import numpy as np

from benchmarks import common
from repro.workloads.tpcw import Interaction

INT_MAX = 2147483647


def _mk_arrivals(rng, gen, light_rate, heavy_rate, duration):
    arr = []
    for t in np.sort(rng.uniform(0, duration,
                                 max(1, int(light_rate * duration)))):
        i = int(gen.rng.integers(0, gen.n_items))
        arr.append((float(t), Interaction(
            "product_detail", [("get_book", {0: (i, i)})], [])))
    for t in np.sort(rng.uniform(0, duration,
                                 int(heavy_rate * duration))):
        lo = max(0, gen._next_order - 3333)
        subj = int(gen.rng.integers(0, 24))
        arr.append((float(t), Interaction(
            "best_sellers",
            [("best_sellers", {0: (lo, INT_MAX), 1: (subj, subj)})], [])))
    arr.sort(key=lambda x: x[0])
    return arr


def run(light_rate=50.0, heavy_rates=(0, 20, 80, 200, 400), duration=12.0,
        seed=13):
    rng = np.random.default_rng(seed)
    plan, shared, baseline, gen = common.build_engines(rng)
    common.warmup(shared, baseline, gen)
    rows = []
    for hr in heavy_rates:
        arr = _mk_arrivals(rng, gen, light_rate, hr, duration)
        rs = common.run_shared(shared, arr, duration)
        arr2 = _mk_arrivals(rng, gen, light_rate, hr, duration)
        rb = common.run_baseline(baseline, arr2, duration)
        rows.append((hr, rs, rb))
        print(f"fig11 heavy={hr:4.0f}/s  "
              f"shared: total_good={rs.good_wips:6.2f}/s p99={rs.p99_s:5.2f} | "
              f"qaat: total_good={rb.good_wips:6.2f}/s p99={rb.p99_s:5.2f}",
              flush=True)
    return rows


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
