"""Fig. 9: max throughput per individual web interaction."""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common
from repro.workloads.tpcw import MIXES


def run(n_per_kind=32, seed=17, kinds=None):
    rng = np.random.default_rng(seed)
    plan, shared, baseline, gen = common.build_engines(rng)
    common.warmup(shared, baseline, gen)
    kinds = kinds or list(MIXES["shopping"])
    rows = []
    for kind in kinds:
        inters = [gen.interaction(kind) for _ in range(n_per_kind)]
        t0 = time.time()
        for it in inters:
            for q in it.queries:
                shared.submit(*q)
            for u in it.updates:
                shared.submit_update(*u)
        shared.run_until_drained()
        wips_s = n_per_kind / (time.time() - t0)
        inters = [gen.interaction(kind) for _ in range(n_per_kind)]
        t0 = time.time()
        for it in inters:
            for u in it.updates:
                baseline.apply_update(*u)
            for q in it.queries:
                baseline.execute(*q)
        wips_b = n_per_kind / (time.time() - t0)
        rows.append((kind, wips_s, wips_b))
        print(f"fig9 {kind:22s} shared={wips_s:8.1f} WIPS  "
              f"qaat={wips_b:8.1f} WIPS  ratio={wips_s/max(wips_b,1e-9):5.2f}",
              flush=True)
    return rows


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
