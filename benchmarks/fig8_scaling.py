"""Fig. 8: throughput scaling with compute units (PROJECTION).

One physical core here, so scaling is projected from the measured
single-core cycle time using the plan's per-node cost breakdown (Amdahl
over operator partitioning/replication, paper §4.3/§4.5): with k units,
cycle_k = t1 * max(largest_node_fraction, 1/k).  The baseline projects
linearly in k (optimistic for it — no contention modeled; the paper shows
MySQL saturating at 12 cores).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common
from repro.core import sla


def run(cores=(1, 2, 4, 8, 16, 32), n=64, mix="shopping", seed=23):
    rng = np.random.default_rng(seed)
    plan, shared, baseline, gen = common.build_engines(rng)
    common.warmup(shared, baseline, gen)

    # measured single-core throughput
    inters = gen.sample_mix(mix, n)
    t0 = time.time()
    for it in inters:
        for q in it.queries:
            shared.submit(*q)
        for u in it.updates:
            shared.submit_update(*u)
    shared.run_until_drained()
    t_shared = (time.time() - t0)
    t0 = time.time()
    for it in inters:
        for u in it.updates:
            baseline.apply_update(*u)
        for q in it.queries:
            baseline.execute(*q)
    t_base = time.time() - t0

    cost = sla.cycle_cost(plan)
    fracs = [v["flops"] for v in cost["nodes"].values()]
    max_frac = max(fracs) / max(sum(fracs), 1e-9)

    rows = []
    for k in cores:
        sh = (n / t_shared) / max(max_frac, 1.0 / k) * 1.0
        ba = (n / t_base) * k
        rows.append((k, sh, ba))
        print(f"fig8 cores={k:3d}  shared={sh:9.1f} WIPS(proj)  "
              f"qaat={ba:9.1f} WIPS(proj)", flush=True)
    print(f"fig8 note: largest-operator fraction={max_frac:.2f} "
          f"(shared-plan Amdahl ceiling)")
    return rows


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
