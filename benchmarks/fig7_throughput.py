"""Fig. 7: throughput under varying load, all three TPC-W mixes.

SharedDB vs query-at-a-time over offered-load sweep; reports good WIPS
(web interactions completing within their TPC-W timeout).
"""
from __future__ import annotations

import numpy as np

from benchmarks import common


def run(rates=(10, 40, 120, 250), duration=10.0,
        mixes=("browsing", "shopping", "ordering"), seed=7):
    rng = np.random.default_rng(seed)
    plan, shared, baseline, gen = common.build_engines(rng)
    common.warmup(shared, baseline, gen)
    rows = []
    for mix in mixes:
        for rate in rates:
            arr, dur = common.poisson_arrivals(rng, gen, mix, rate, duration)
            rs = common.run_shared(shared, arr, dur)
            arr2, _ = common.poisson_arrivals(rng, gen, mix, rate, duration)
            rb = common.run_baseline(baseline, arr2, dur)
            rows.append((mix, rate, rs, rb))
            print(f"fig7 {mix:9s} rate={rate:3d}/s  "
                  f"shared: good={rs.good_wips:6.2f} p99={rs.p99_s:6.2f}s "
                  f"cyc={rs.mean_cycle_s*1e3:6.0f}ms | "
                  f"qaat: good={rb.good_wips:6.2f} p99={rb.p99_s:6.2f}s",
                  flush=True)
    return rows


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
